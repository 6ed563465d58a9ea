"""Faults planted under the timed path, to show that the checks catch
them (``benchmark/tests/test_faults.py`` on the CPU, ``control.py`` on
the chip). A run with a fault planted must come out not correct.

    parity      one byte of the first parity shard flipped where the
                OSD's write path produces it (ec_util encode and
                encode_fused)
    unapplied   writes of the window acknowledged, but no store applies
                them: the state stays as it was
    read        one byte of every read flipped where the primary
                reassembles it (ec_util.decode_concat)
    half_batch  the codec's batched encode leaves the second half of
                the batch out (its parity comes back zero)
    codec_byte  one byte of every codec decode result flipped
"""

from __future__ import annotations

import contextlib

import numpy as np


def _flip(buf):
    arr = np.array(buf, dtype=np.uint8, copy=True).reshape(-1)
    arr[arr.size // 2] ^= 0x5A
    return arr


@contextlib.contextmanager
def _patched(owner, attr, wrap):
    orig = getattr(owner, attr)
    setattr(owner, attr, wrap(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def _parity():
    from ceph_tpu.osd import ec_util

    def on_map(shards, codec):
        first = codec.chunk_index(codec.get_data_chunk_count())
        if first in shards:
            shards[first] = _flip(shards[first])
        return shards

    def wrap_encode(orig):
        def encode(sinfo, codec, data, *a, **kw):
            return on_map(orig(sinfo, codec, data, *a, **kw), codec)
        return encode

    def wrap_fused(orig):
        def encode_fused(sinfo, codec, data, *a, **kw):
            shards, r = orig(sinfo, codec, data, *a, **kw)
            return on_map(shards, codec), r
        return encode_fused
    stack = contextlib.ExitStack()
    stack.enter_context(_patched(ec_util, "encode", wrap_encode))
    stack.enter_context(_patched(ec_util, "encode_fused", wrap_fused))
    return stack


def _unapplied():
    from ceph_tpu.store.mem_store import MemStore

    def wrap(orig):
        def queue_transaction(self, txn):
            if any(len(op) > 2 and "bench_w_" in str(op[2])
                   for op in txn.ops):
                for cb in list(txn.on_applied) + list(txn.on_commit):
                    self._complete(cb)
                return
            return orig(self, txn)
        return queue_transaction
    return _patched(MemStore, "queue_transaction", wrap)


def _read():
    from ceph_tpu.osd import ec_util

    def wrap(orig):
        def decode_concat(*a, **kw):
            return _flip(np.frombuffer(orig(*a, **kw),
                                       dtype=np.uint8)).tobytes()
        return decode_concat
    return _patched(ec_util, "decode_concat", wrap)


def _half_batch():
    from ceph_tpu.models.matrix_base import GeneratorCodec

    def wrap(orig):
        def encode_batch(self, data):
            out = np.array(orig(self, data), copy=True)
            out[out.shape[0] // 2:] = 0
            return out
        return encode_batch
    return _patched(GeneratorCodec, "encode_batch", wrap)


def _codec_byte():
    from ceph_tpu.models.matrix_base import GeneratorCodec

    def wrap(orig):
        def decode(self, want, chunks):
            out = dict(orig(self, want, chunks))
            c = min(want)
            out[c] = _flip(out[c])
            return out
        return decode
    return _patched(GeneratorCodec, "decode", wrap)


FAULTS = {"parity": _parity, "unapplied": _unapplied, "read": _read,
          "half_batch": _half_batch, "codec_byte": _codec_byte}


def planted(name: str):
    """A context manager under which the fault `name` is in place."""
    return FAULTS[name]()
