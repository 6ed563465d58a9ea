"""subreads_per_read.read: mean number of sub-reads an EC client read
sent, over the window's reads: the `sub_read(shard=N)` children of
`read_gather` in each read's tree (osd/ec_backend.py,
benchmark/spans.py), a re-planned read's extra ones included. A whole
4 MiB object read touches all k columns: k (8 at RS k=8), with data
shards down or not. None on a run without op spans."""

from benchmark import readers, spans


def read(run):
    reads = spans.ops(run, "read")
    if not reads:
        return None
    return sum(sum(readers.shard_of(s["name"]) is not None for s in group)
               for group in reads) / len(reads)
