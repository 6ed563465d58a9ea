"""subreads_per_read.rbd: mean number of sub-reads an EC client read
sent, over the window's reads: the `sub_read(shard=N)` children of
`read_gather` in each read's tree (osd/ec_backend.py,
benchmark/spans.py), a re-planned read's extra ones included. A 4 KiB
read of a k=4 stripe of 4 KiB chunks that asks only the shard holding
its bytes reads 1; one that asks the whole stripe, 4. None on a run
without op spans."""

from benchmark import readers, spans


def read(run):
    reads = spans.ops(run, "read")
    if not reads:
        return None
    return sum(sum(readers.shard_of(s["name"]) is not None for s in group)
               for group in reads) / len(reads)
