"""ec_decode_ms.read: mean `ec_decode` span (osd/ec_backend.py:
reassembly of a read from its shards) over the reads of the window that
rebuilt, i.e. read a shard that holds no data (a parity shard)."""

from benchmark import readers


def read(run):
    data = set(run.code.data_positions)
    rebuilt = {s["trace_id"] for s in run.spans
               if readers.shard_of(s["name"]) is not None
               and readers.shard_of(s["name"]) not in data}
    return readers.span_mean_ms(run, "ec_decode",
                                keep=lambda s: s["trace_id"] in rebuilt)
