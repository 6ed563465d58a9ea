"""decode_roofline.read: share of the chip's roofline reached by the
decode programs of degraded reads (the codec's decode_batch, which
runs ops/xor_mm.py matrix_encode with the decode matrix) in the
window, in %.

Work per read rebuilt on the device: its k surviving shards read and
its lost data shards written (bytes), and a lost x k GF(2^8) matrix
applied to the survivors (operations, `readers.gf_ops`); the bytes
bind. A read rebuilds on the device when its
object lost two or more data shards, or one while the all-ones parity
shard (the first parity position) is lost too; one lost data shard
beside a live all-ones parity shard is rebuilt by the codec's host XOR
shortcut and never reaches the device. Which shards a read lost
follows from the object's acting set and the stopped OSDs.
"""

from benchmark import readers, trace

PROGRAMS = [r"^jit_matrix_encode$"]


def work_bytes(k: int, lost: int, shard_bytes: int) -> int:
    return (k + lost) * shard_bytes


def work_ops(k: int, lost: int, shard_bytes: int) -> int:
    return readers.gf_ops(lost, k, shard_bytes)


def on_device(lost_data: int, xor_parity_lost: bool) -> bool:
    return lost_data >= 2 or (lost_data == 1 and xor_parity_lost)


def read(run):
    if run.trace is None or not run.down:
        return None
    ex = trace.executions(run.trace, PROGRAMS)
    code, down = run.code, set(run.down)
    shard_bytes = int(run.traffic["object_size"]) // code.k
    total = ops = 0
    for op in run.ops:
        if op.kind != "read" or not op.ok or op.end > run.t1:
            continue
        acting = run.placement[op.obj]
        lost = sum(acting[p] in down for p in code.data_positions)
        if on_device(lost, acting[code.parity_positions[0]] in down):
            total += work_bytes(code.k, lost, shard_bytes)
            ops += work_ops(code.k, lost, shard_bytes)
    if not ex or not total:
        return None
    return readers.roofline_pct(total, ops, readers.device_seconds(ex), run)
