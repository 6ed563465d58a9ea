"""ec_host_ms.write: mean ms per client write of the EC backend's host
work inside `ec_encode` on its critical path: `ec_assemble` (the
logical buffer), `ec_hinfo` (host shard crcs) and `ec_txns` (chunk
split, shard writes, attrs), osd/ec_transaction.py
(benchmark/spans.py)."""

from benchmark import spans


def read(run):
    return spans.stage_ms(run, "write",
                          ("ec_assemble", "ec_hinfo", "ec_txns"))
