"""CRUSH differential tests: Python/JAX reimplementation vs the reference
C core compiled at test time (bit-exactness is the contract — BASELINE.json
correctness gate: batched mapping exhaustively equal to crush_do_rule).
"""

import numpy as np
import pytest

from ceph_tpu.crush import batched, hashing, ln, map as cmap_mod, mapper_ref
from ceph_tpu.crush.map import CrushMap, Rule, CRUSH_ITEM_NONE

from . import crush_oracle

ALG_UNIFORM, ALG_LIST, ALG_STRAW2 = 1, 2, 5
OP_CHOOSE_FIRSTN, OP_CHOOSE_INDEP = 2, 3
OP_CHOOSELEAF_FIRSTN, OP_CHOOSELEAF_INDEP = 6, 7
TUN_DEFAULT = [51, 0, 0, 1, 1, 1]  # total_tries+1 handled in C; see below


def lib_or_skip():
    lib = crush_oracle.get_oracle()
    if lib is None:
        pytest.skip("reference C oracle unavailable")
    return lib


def make_two_level(num_hosts, devs_per_host, dev_weights, leaf_alg="straw2"):
    m = CrushMap()
    m.type_names = {"osd": 0, "host": 1, "root": 2}
    host_ids = []
    host_weights = []
    for h in range(num_hosts):
        items = [h * devs_per_host + i for i in range(devs_per_host)]
        w = [int(dev_weights[i]) for i in items]
        hid = m.add_bucket(leaf_alg, 1, items, w, id=-2 - h)
        host_ids.append(hid)
        host_weights.append(sum(w))
    m.add_bucket("straw2", 2, host_ids, host_weights, id=-1, name="default")
    return m


def make_flat(ndev, dev_weights, leaf_alg="straw2"):
    m = CrushMap()
    m.type_names = {"osd": 0, "host": 1}
    m.add_bucket(leaf_alg, 1, list(range(ndev)),
                 [int(w) for w in dev_weights], id=-1, name="default")
    return m


def crush_tunables(m):
    t = m.tunables
    return [t.choose_total_tries, t.choose_local_tries,
            t.choose_local_fallback_tries, t.chooseleaf_descend_once,
            t.chooseleaf_vary_r, t.chooseleaf_stable]


def test_crush_ln_full_domain():
    lib = lib_or_skip()
    ref = np.array([lib.oracle_crush_ln(u) for u in range(0x10000)],
                   dtype=np.int64)
    assert np.array_equal(np.asarray(ln.crush_ln(np.arange(0x10000))), ref)


def test_crush_ln_jax_full_domain():
    lib = lib_or_skip()
    import jax
    import jax.numpy as jnp
    ref = np.array([lib.oracle_crush_ln(u) for u in range(0x10000)],
                   dtype=np.int64)
    with jax.enable_x64():
        out = jax.jit(lambda u: ln.crush_ln(u, xp=jnp))(jnp.arange(0x10000))
    assert np.array_equal(np.asarray(out), ref)


def test_rjenkins_hashes():
    lib = lib_or_skip()
    rng = np.random.default_rng(0)
    abc = rng.integers(0, 2**32, size=(300, 3), dtype=np.uint64).astype(
        np.uint32)
    with np.errstate(over="ignore"):
        m2 = np.asarray(hashing.hash32_2(abc[:, 0], abc[:, 1]))
        m3 = np.asarray(hashing.hash32_3(abc[:, 0], abc[:, 1], abc[:, 2]))
        m4 = np.asarray(hashing.hash32_4(abc[:, 0], abc[:, 1], abc[:, 2],
                                         abc[:, 0] ^ abc[:, 1]))
    for i, (a, b, c) in enumerate(abc):
        assert m2[i] == lib.oracle_hash32_2(int(a), int(b))
        assert m3[i] == lib.oracle_hash32_3(int(a), int(b), int(c))
        assert m4[i] == lib.oracle_hash32_4(int(a), int(b), int(c),
                                            int(a) ^ int(b))


@pytest.mark.parametrize("op,steps_op", [
    (OP_CHOOSE_INDEP, cmap_mod.RULE_CHOOSE_INDEP),
    (OP_CHOOSE_FIRSTN, cmap_mod.RULE_CHOOSE_FIRSTN),
])
def test_flat_bucket_vs_oracle(op, steps_op):
    lib = lib_or_skip()
    rng = np.random.default_rng(1)
    ndev = 12
    weights = rng.integers(1, 4 * 0x10000, size=ndev, dtype=np.uint32)
    reweight = np.full(ndev, 0x10000, dtype=np.uint32)
    reweight[3] = 0            # marked out
    reweight[7] = 0x8000       # half reweighted
    m = make_flat(ndev, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1), (steps_op, 3, 0),
                           (cmap_mod.RULE_EMIT,)]))
    for x in range(60):
        ref = crush_oracle.oracle_map_run(
            lib, ALG_STRAW2, 1, ndev, weights, 1, op, 0, 3, x,
            reweight, crush_tunables(m), 3)
        mine = mapper_ref.crush_do_rule(m, 0, x, 3, list(reweight))
        assert mine == ref, (x, mine, ref)


@pytest.mark.parametrize("op,steps_op,leaf_alg,calg", [
    (OP_CHOOSELEAF_INDEP, cmap_mod.RULE_CHOOSELEAF_INDEP, "straw2", ALG_STRAW2),
    (OP_CHOOSELEAF_FIRSTN, cmap_mod.RULE_CHOOSELEAF_FIRSTN, "straw2", ALG_STRAW2),
    (OP_CHOOSELEAF_INDEP, cmap_mod.RULE_CHOOSELEAF_INDEP, "list", ALG_LIST),
    (OP_CHOOSELEAF_INDEP, cmap_mod.RULE_CHOOSELEAF_INDEP, "uniform", ALG_UNIFORM),
])
def test_two_level_chooseleaf_vs_oracle(op, steps_op, leaf_alg, calg):
    lib = lib_or_skip()
    rng = np.random.default_rng(2)
    hosts, per = 5, 4
    ndev = hosts * per
    if leaf_alg == "uniform":
        weights = np.full(ndev, 0x10000, dtype=np.uint32)
    else:
        weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    reweight = np.full(ndev, 0x10000, dtype=np.uint32)
    reweight[5] = 0
    reweight[11] = 0x4000
    m = make_two_level(hosts, per, weights, leaf_alg=leaf_alg)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1), (steps_op, 4, 1),
                           (cmap_mod.RULE_EMIT,)]))
    for x in range(40):
        ref = crush_oracle.oracle_map_run(
            lib, calg, hosts, per, weights, 0, op, 1, 4, x,
            reweight, crush_tunables(m), 4)
        mine = mapper_ref.crush_do_rule(m, 0, x, 4, list(reweight))
        assert mine == ref, (leaf_alg, x, mine, ref)


def test_legacy_tunables_vs_oracle():
    # pre-jewel tunables: local retries + fallback + vary_r=0 + stable=0
    lib = lib_or_skip()
    rng = np.random.default_rng(3)
    hosts, per = 4, 3
    ndev = hosts * per
    weights = rng.integers(1, 2 * 0x10000, size=ndev, dtype=np.uint32)
    reweight = np.full(ndev, 0x10000, dtype=np.uint32)
    reweight[2] = 0
    m = make_two_level(hosts, per, weights)
    m.tunables = cmap_mod.Tunables(
        choose_total_tries=19, choose_local_tries=2,
        choose_local_fallback_tries=5, chooseleaf_descend_once=0,
        chooseleaf_vary_r=0, chooseleaf_stable=0)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_FIRSTN, 3, 1),
                           (cmap_mod.RULE_EMIT,)]))
    for x in range(40):
        ref = crush_oracle.oracle_map_run(
            lib, ALG_STRAW2, hosts, per, weights, 0,
            OP_CHOOSELEAF_FIRSTN, 1, 3, x, reweight, crush_tunables(m), 3)
        mine = mapper_ref.crush_do_rule(m, 0, x, 3, list(reweight))
        assert mine == ref, (x, mine, ref)


def test_batched_matches_ref_flat_indep():
    rng = np.random.default_rng(4)
    ndev = 10
    weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    m = make_flat(ndev, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_INDEP, 4, 0),
                           (cmap_mod.RULE_EMIT,)]))
    reweight = np.full(ndev, 0x10000, dtype=np.int64)
    reweight[1] = 0
    reweight[8] = 0x9000
    xs = np.arange(300)
    got = batched.batched_do_rule(m, 0, xs, 4, reweight)
    for x in xs:
        ref = mapper_ref.crush_do_rule(m, 0, int(x), 4, list(reweight))
        assert list(got[x]) == ref, (x, list(got[x]), ref)


def test_batched_matches_ref_two_level_chooseleaf_indep():
    # the EC placement shape: take root -> chooseleaf indep over hosts
    rng = np.random.default_rng(5)
    hosts, per = 6, 4
    ndev = hosts * per
    weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    m = make_two_level(hosts, per, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_INDEP, 5, 1),
                           (cmap_mod.RULE_EMIT,)]))
    reweight = np.full(ndev, 0x10000, dtype=np.int64)
    reweight[0] = 0
    reweight[13] = 0x2000
    xs = np.arange(300)
    got = batched.batched_do_rule(m, 0, xs, 5, reweight)
    for x in xs:
        ref = mapper_ref.crush_do_rule(m, 0, int(x), 5, list(reweight))
        assert list(got[x]) == ref, (x, list(got[x]), ref)


def test_batched_indep_holes_are_positional():
    # indep leaves CRUSH_ITEM_NONE holes rather than shifting (required by
    # EC shard positioning, ecbackend.rst:100-105)
    m = make_flat(4, [0x10000] * 4)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_INDEP, 4, 0),
                           (cmap_mod.RULE_EMIT,)]))
    # mark two devices fully out: only 2 of 4 slots can fill
    reweight = np.array([0x10000, 0, 0x10000, 0], dtype=np.int64)
    got = batched.batched_do_rule(m, 0, np.arange(50), 4, reweight)
    ref_holes = 0
    for row in got:
        for v in row:
            assert v in (0, 2, CRUSH_ITEM_NONE)
        ref_holes += sum(1 for v in row if v == CRUSH_ITEM_NONE)
    assert ref_holes == 50 * 2  # exactly the out devices leave holes


def test_create_rule_integration():
    # ErasureCode.create_rule analog: codec geometry drives rule creation
    from ceph_tpu import registry
    codec = registry.factory("jax_tpu", {"technique": "reed_sol_van",
                                         "k": "4", "m": "2", "w": "8"})
    m = make_two_level(8, 2, [0x10000] * 16)
    ruleno = m.add_simple_rule("ecpool", "default", "host", mode="indep",
                               rule_type=cmap_mod.POOL_TYPE_ERASURE)
    res = batched.batched_do_rule(m, ruleno, np.arange(20),
                                  codec.get_chunk_count())
    assert res.shape == (20, 6)
    for row in res:
        real = [v for v in row if v != CRUSH_ITEM_NONE]
        assert len(set(real)) == len(real)  # distinct devices


@pytest.mark.parametrize("op1,op2,pop1,pop2", [
    (OP_CHOOSE_FIRSTN, OP_CHOOSE_FIRSTN,
     cmap_mod.RULE_CHOOSE_FIRSTN, cmap_mod.RULE_CHOOSE_FIRSTN),
    (OP_CHOOSE_INDEP, OP_CHOOSE_INDEP,
     cmap_mod.RULE_CHOOSE_INDEP, cmap_mod.RULE_CHOOSE_INDEP),
])
def test_two_step_rule_vs_oracle(op1, op2, pop1, pop2):
    # multi-bucket working vector: choose N hosts, then 1 osd per host
    # (exercises the o+osize slice semantics of crush_do_rule:1019-1056)
    lib = lib_or_skip()
    rng = np.random.default_rng(7)
    hosts, per = 5, 3
    ndev = hosts * per
    weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    reweight = np.full(ndev, 0x10000, dtype=np.uint32)
    reweight[4] = 0
    m = make_two_level(hosts, per, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1), (pop1, 3, 1),
                           (pop2, 1, 0), (cmap_mod.RULE_EMIT,)]))
    for x in range(40):
        ref = crush_oracle.oracle_map_run(
            lib, ALG_STRAW2, hosts, per, weights, 0, op1, 1, 3, x,
            reweight, crush_tunables(m), 3, rule_op2=op2, choose_type2=0,
            numrep2=1)
        mine = mapper_ref.crush_do_rule(m, 0, x, 3, list(reweight))
        assert mine == ref, (x, mine, ref)


def test_numrep_exceeds_result_max_vs_oracle():
    # C keeps the rule numrep as the retry stride even when result_max
    # truncates the output count (mapper.c:1039-1046)
    lib = lib_or_skip()
    rng = np.random.default_rng(8)
    ndev = 10
    weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    reweight = np.full(ndev, 0x10000, dtype=np.uint32)
    reweight[2] = 0
    m = make_flat(ndev, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_INDEP, 6, 0),
                           (cmap_mod.RULE_EMIT,)]))
    for x in range(40):
        ref = crush_oracle.oracle_map_run(
            lib, ALG_STRAW2, 1, ndev, weights, 1, OP_CHOOSE_INDEP, 0, 6, x,
            reweight, crush_tunables(m), 4)
        mine = mapper_ref.crush_do_rule(m, 0, x, 4, list(reweight))
        assert mine == ref, (x, mine, ref)
    # batched fast path agrees too
    got = batched.batched_do_rule(m, 0, np.arange(40), 4,
                                  np.asarray(reweight, dtype=np.int64))
    for x in range(40):
        ref = mapper_ref.crush_do_rule(m, 0, x, 4, list(reweight))
        assert list(got[x]) == ref, (x, list(got[x]), ref)


def test_batched_device_at_root_level_permanent_none():
    # a device directly under the root alongside host buckets: chooseleaf
    # over hosts must mark reps landing on the device as permanent NONE
    # (mapper.c:744-751), in both the interpreter and the batched kernel
    m = CrushMap()
    m.type_names = {"osd": 0, "host": 1, "root": 2}
    m.add_bucket("straw2", 1, [0, 1], [0x10000, 0x10000], id=-2)
    m.add_bucket("straw2", 1, [2, 3], [0x10000, 0x10000], id=-3)
    # root holds two hosts AND a bare device 4
    m.add_bucket("straw2", 2, [-2, -3, 4], [0x20000, 0x20000, 0x10000],
                 id=-1, name="default")
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_INDEP, 3, 1),
                           (cmap_mod.RULE_EMIT,)]))
    xs = np.arange(200)
    got = batched.batched_do_rule(m, 0, xs, 3)
    saw_hole = False
    for x in xs:
        ref = mapper_ref.crush_do_rule(m, 0, int(x), 3)
        assert list(got[x]) == ref, (x, list(got[x]), ref)
        saw_hole = saw_hole or CRUSH_ITEM_NONE in ref
    assert saw_hole  # the bare device must have produced permanent holes


def test_batched_malformed_map_falls_back():
    # dangling bucket reference: batched path must degrade like the
    # scalar interpreter (holes), not crash
    m = CrushMap()
    m.type_names = {"osd": 0, "host": 1, "root": 2}
    m.add_bucket("straw2", 1, [0, 1], [0x10000] * 2, id=-2)
    m.add_bucket("straw2", 2, [-2, -9], [0x20000, 0x20000], id=-1,
                 name="default")
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_INDEP, 2, 1),
                           (cmap_mod.RULE_EMIT,)]))
    got = batched.batched_do_rule(m, 0, np.arange(20), 2)
    for x in range(20):
        ref = mapper_ref.crush_do_rule(m, 0, x, 2)
        assert list(got[x]) == ref


def test_batched_matches_ref_flat_firstn():
    # the replicated-pool shape: choose firstn over devices
    rng = np.random.default_rng(6)
    ndev = 10
    weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    m = make_flat(ndev, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_FIRSTN, 3, 0),
                           (cmap_mod.RULE_EMIT,)]))
    reweight = np.full(ndev, 0x10000, dtype=np.int64)
    reweight[2] = 0
    reweight[7] = 0x8000
    xs = np.arange(300)
    got = batched.batched_do_rule(m, 0, xs, 3, reweight)
    for x in xs:
        ref = mapper_ref.crush_do_rule(m, 0, int(x), 3, list(reweight))
        mine = [int(v) for v in got[x] if v != CRUSH_ITEM_NONE]
        assert mine == ref, (x, mine, ref)


def test_batched_matches_ref_two_level_chooseleaf_firstn():
    # the canonical replicated rule: take root -> chooseleaf firstn
    # over hosts -> emit (CrushWrapper::add_simple_rule default)
    rng = np.random.default_rng(7)
    hosts, per = 6, 4
    ndev = hosts * per
    weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    m = make_two_level(hosts, per, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_FIRSTN, 3, 1),
                           (cmap_mod.RULE_EMIT,)]))
    reweight = np.full(ndev, 0x10000, dtype=np.int64)
    reweight[5] = 0
    reweight[16] = 0x4000
    xs = np.arange(300)
    got = batched.batched_do_rule(m, 0, xs, 3, reweight)
    for x in xs:
        ref = mapper_ref.crush_do_rule(m, 0, int(x), 3, list(reweight))
        mine = [int(v) for v in got[x] if v != CRUSH_ITEM_NONE]
        assert mine == ref, (x, mine, ref)


def test_batched_firstn_compacts_not_holes():
    # firstn output shifts out devices away (can_shift_osds), unlike
    # indep's positional holes
    m = make_flat(4, [0x10000] * 4)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_FIRSTN, 4, 0),
                           (cmap_mod.RULE_EMIT,)]))
    reweight = np.array([0x10000, 0, 0x10000, 0], dtype=np.int64)
    got = batched.batched_do_rule(m, 0, np.arange(50), 4, reweight)
    for x in range(50):
        ref = mapper_ref.crush_do_rule(m, 0, x, 4, list(reweight))
        mine = [int(v) for v in got[x] if v != CRUSH_ITEM_NONE]
        assert mine == ref
        # holes only at the tail (compacted prefix)
        row = list(got[x])
        assert row[:len(mine)] == mine


def test_batched_firstn_numrep_exceeds_available():
    m = make_two_level(3, 2, [0x10000] * 6)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_FIRSTN, 5, 1),
                           (cmap_mod.RULE_EMIT,)]))
    got = batched.batched_do_rule(m, 0, np.arange(100), 5)
    for x in range(100):
        ref = mapper_ref.crush_do_rule(m, 0, x, 5, None)
        mine = [int(v) for v in got[x] if v != CRUSH_ITEM_NONE]
        assert mine == ref, (x, mine, ref)


def test_batched_firstn_exotic_tunables_fall_back():
    # non-jewel local retries ride the scalar interpreter
    m = make_flat(6, [0x10000] * 6)
    m.tunables.choose_local_tries = 2
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_FIRSTN, 3, 0),
                           (cmap_mod.RULE_EMIT,)]))
    got = batched.batched_do_rule(m, 0, np.arange(30), 3)
    for x in range(30):
        ref = mapper_ref.crush_do_rule(m, 0, x, 3, None)
        mine = [int(v) for v in got[x] if v != CRUSH_ITEM_NONE]
        assert mine == ref


def test_batched_firstn_bucket_target_ignores_device_reweight():
    # choose firstn emitting BUCKETS: is_out applies to devices only
    # (mapper.c:581-585); reweight must not reject host buckets
    m = make_two_level(4, 2, [0x10000] * 8)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_FIRSTN, 2, 1),
                           (cmap_mod.RULE_EMIT,)]))
    reweight = np.full(8, 0x10000, dtype=np.int64)
    reweight[0] = 0
    got = batched.batched_do_rule(m, 0, np.arange(20), 2, reweight)
    for x in range(20):
        ref = mapper_ref.crush_do_rule(m, 0, x, 2, list(reweight))
        mine = [int(v) for v in got[x] if v != CRUSH_ITEM_NONE]
        assert mine == ref, (x, mine, ref)


# -- choose_args (weight-sets / ids) differential tests ------------------

@pytest.mark.parametrize("op,steps_op,positions,with_ids", [
    (OP_CHOOSE_INDEP, cmap_mod.RULE_CHOOSE_INDEP, 1, False),
    (OP_CHOOSE_INDEP, cmap_mod.RULE_CHOOSE_INDEP, 3, True),
    (OP_CHOOSE_FIRSTN, cmap_mod.RULE_CHOOSE_FIRSTN, 1, True),
    (OP_CHOOSE_FIRSTN, cmap_mod.RULE_CHOOSE_FIRSTN, 3, False),
])
def test_choose_args_flat_vs_oracle(op, steps_op, positions, with_ids):
    """Weight-set + ids substitution in a flat straw2 bucket must be
    bit-equal to the reference's bucket_straw2_choose with
    crush_choose_arg (mapper.c:302-341, 459-512)."""
    lib = lib_or_skip()
    rng = np.random.default_rng(21)
    ndev = 10
    weights = rng.integers(1, 4 * 0x10000, size=ndev, dtype=np.uint32)
    reweight = np.full(ndev, 0x10000, dtype=np.uint32)
    reweight[2] = 0x8000
    m = make_flat(ndev, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1), (steps_op, 3, 0),
                           (cmap_mod.RULE_EMIT,)]))
    ws = rng.integers(0, 5 * 0x10000, size=(positions, ndev),
                      dtype=np.uint32)
    ws[:, 0] = 0x10000  # keep at least one nonzero weight everywhere
    ids = (rng.permutation(ndev).astype(np.int32) + 100) if with_ids \
        else None
    cargs = {-1: {"weight_set": [[int(w) for w in row] for row in ws],
                  "ids": [int(i) for i in ids] if ids is not None
                  else None}}
    mask = [1 | (2 if with_ids else 0)]
    ws_flat = ws.reshape(-1)
    ids_flat = ids if ids is not None else np.zeros(0, dtype=np.int32)
    for x in range(80):
        ref = crush_oracle.oracle_map_run_cargs(
            lib, ALG_STRAW2, 1, ndev, weights, 1, op, 0, 3, x,
            reweight, crush_tunables(m), 3,
            positions, mask, ws_flat, ids_flat)
        mine = mapper_ref.crush_do_rule(m, 0, x, 3, list(reweight),
                                        choose_args=cargs)
        assert mine == ref, (x, mine, ref)


@pytest.mark.parametrize("op,steps_op", [
    (OP_CHOOSELEAF_INDEP, cmap_mod.RULE_CHOOSELEAF_INDEP),
    (OP_CHOOSELEAF_FIRSTN, cmap_mod.RULE_CHOOSELEAF_FIRSTN),
])
def test_choose_args_two_level_chooseleaf_vs_oracle(op, steps_op):
    """Weight-sets on BOTH the root and the host buckets through a
    chooseleaf descent (positions > 1 exercises the per-outpos weight
    selection and its clamp)."""
    lib = lib_or_skip()
    rng = np.random.default_rng(22)
    hosts, per, positions = 5, 4, 2
    ndev = hosts * per
    weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    reweight = np.full(ndev, 0x10000, dtype=np.uint32)
    reweight[7] = 0
    m = make_two_level(hosts, per, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1), (steps_op, 4, 1),
                           (cmap_mod.RULE_EMIT,)]))
    # root weight-set (over hosts) + per-host weight-sets (over devs)
    root_ws = rng.integers(0x8000, 4 * 0x10000, size=(positions, hosts),
                           dtype=np.uint32)
    host_ws = [rng.integers(0x4000, 3 * 0x10000, size=(positions, per),
                            dtype=np.uint32) for _ in range(hosts)]
    cargs = {-1: {"weight_set": [[int(w) for w in row]
                                 for row in root_ws], "ids": None}}
    for h in range(hosts):
        cargs[-2 - h] = {"weight_set": [[int(w) for w in row]
                                        for row in host_ws[h]],
                         "ids": None}
    mask = [1] * (1 + hosts)
    ws_flat = np.concatenate([root_ws.reshape(-1)]
                             + [hw.reshape(-1) for hw in host_ws])
    ids_flat = np.zeros(0, dtype=np.int32)
    for x in range(50):
        ref = crush_oracle.oracle_map_run_cargs(
            lib, ALG_STRAW2, hosts, per, weights, 0, op, 1, 4, x,
            reweight, crush_tunables(m), 4,
            positions, mask, ws_flat, ids_flat)
        mine = mapper_ref.crush_do_rule(m, 0, x, 4, list(reweight),
                                        choose_args=cargs)
        assert mine == ref, (x, mine, ref)


def test_choose_args_balancer_remap_without_base_weights():
    """The balancer contract: adjusting a weight-set copy remaps PGs
    while the bucket's base weights are untouched, and dropping the
    weight-set restores the original mapping (CrushWrapper
    create_choose_args / choose_args_adjust_item_weight roles)."""
    rng = np.random.default_rng(23)
    ndev = 8
    weights = rng.integers(0x10000, 3 * 0x10000, size=ndev,
                           dtype=np.uint32)
    m = make_flat(ndev, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_FIRSTN, 3, 0),
                           (cmap_mod.RULE_EMIT,)]))
    base_weights = m.buckets[-1].weights.copy()
    before = [mapper_ref.crush_do_rule(m, 0, x, 3) for x in range(100)]
    m.create_choose_args(cmap_mod.DEFAULT_CHOOSE_ARGS, positions=1)
    # nudge one overloaded device down hard in the weight-set copy
    m.choose_args_adjust_item_weight(cmap_mod.DEFAULT_CHOOSE_ARGS,
                                     -1, 0, 0x1000)
    after = [mapper_ref.crush_do_rule(
        m, 0, x, 3, choose_args=cmap_mod.DEFAULT_CHOOSE_ARGS)
        for x in range(100)]
    assert np.array_equal(m.buckets[-1].weights, base_weights)
    assert before != after          # the weight-set change remapped
    moved = sum(1 for b, a in zip(before, after) if b != a)
    assert moved > 0
    # osd 0 loses load under the new weight-set
    cnt_before = sum(r.count(0) for r in before)
    cnt_after = sum(r.count(0) for r in after)
    assert cnt_after < cnt_before
    # dropping the set restores the base mapping
    m.choose_args.clear()
    restored = [mapper_ref.crush_do_rule(
        m, 0, x, 3, choose_args=cmap_mod.DEFAULT_CHOOSE_ARGS)
        for x in range(100)]
    assert restored == before


@pytest.mark.parametrize("steps_op,positions,with_ids", [
    (cmap_mod.RULE_CHOOSE_INDEP, 1, True),
    (cmap_mod.RULE_CHOOSE_FIRSTN, 1, False),
    (cmap_mod.RULE_CHOOSE_FIRSTN, 3, True),
    (cmap_mod.RULE_CHOOSELEAF_INDEP, 2, False),
    (cmap_mod.RULE_CHOOSELEAF_FIRSTN, 2, False),
])
def test_batched_choose_args_matches_scalar(steps_op, positions,
                                            with_ids):
    """The device kernels' choose_args path (hash-id substitution,
    per-position weight-set tensor, live-outpos selection in firstn)
    must be bit-equal to the scalar interpreter — which is itself
    oracle-verified above."""
    rng = np.random.default_rng(31)
    chooseleaf = steps_op in (cmap_mod.RULE_CHOOSELEAF_INDEP,
                              cmap_mod.RULE_CHOOSELEAF_FIRSTN)
    if chooseleaf:
        hosts, per = 5, 3
        ndev = hosts * per
        weights = rng.integers(0x8000, 3 * 0x10000, size=ndev,
                               dtype=np.uint32)
        m = make_two_level(hosts, per, weights)
        buckets = [-1] + [-2 - h for h in range(hosts)]
        ctype = 1
    else:
        ndev = 9
        weights = rng.integers(0x8000, 3 * 0x10000, size=ndev,
                               dtype=np.uint32)
        m = make_flat(ndev, weights)
        buckets = [-1]
        ctype = 0
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (steps_op, 3, ctype),
                           (cmap_mod.RULE_EMIT,)]))
    cargs = {}
    for bid in buckets:
        bsz = m.buckets[bid].size
        ws = rng.integers(0x2000, 4 * 0x10000, size=(positions, bsz))
        ids = ([int(i) + 50 for i in
                rng.permutation(bsz)] if with_ids and bid == -1
               else None)
        cargs[bid] = {"weight_set": [[int(w) for w in row]
                                     for row in ws], "ids": ids}
    reweight = np.full(ndev, 0x10000, dtype=np.int64)
    reweight[1] = 0x9000
    xs = np.arange(120)
    got = batched.batched_do_rule(m, 0, xs, 3, list(reweight),
                                  choose_args=cargs)
    for i, x in enumerate(xs):
        ref = mapper_ref.crush_do_rule(m, 0, int(x), 3, list(reweight),
                                       choose_args=cargs)
        mine = [v for v in got[i] if v != CRUSH_ITEM_NONE] \
            if steps_op in (cmap_mod.RULE_CHOOSE_FIRSTN,
                            cmap_mod.RULE_CHOOSELEAF_FIRSTN) else list(got[i])
        if steps_op in (cmap_mod.RULE_CHOOSE_INDEP,
                        cmap_mod.RULE_CHOOSELEAF_INDEP):
            ref = ref + [CRUSH_ITEM_NONE] * (3 - len(ref))
        assert mine == ref, (x, list(got[i]), ref)


def test_choose_args_adjust_propagates_to_ancestors():
    """choose_args_adjust_item_weight writes every position and
    propagates the bucket's per-position totals into ancestor
    weight-sets (CrushWrapper::choose_args_adjust_item_weightf walks
    the parents) — draining a device must shed load at the ROOT draw
    too, not just inside its host."""
    rng = np.random.default_rng(61)
    hosts, per = 3, 2
    weights = np.full(hosts * per, 0x10000, dtype=np.uint32)
    m = make_two_level(hosts, per, weights)
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_FIRSTN, 2, 1),
                           (cmap_mod.RULE_EMIT,)]))
    m.create_choose_args(0, positions=2)
    m.choose_args_adjust_item_weight(0, -2, 0, 0)   # drain osd.0
    arg_host = m.choose_args[0][-2]
    assert all(row[0] == 0 for row in arg_host["weight_set"])   # all positions
    arg_root = m.choose_args[0][-1]
    # host0's total dropped to per-1 devices' worth in the root's set
    assert all(row[0] == 0x10000 for row in arg_root["weight_set"])
    assert all(row[1] == 2 * 0x10000 for row in arg_root["weight_set"])


def test_choose_args_bad_sizes_rejected():
    rng = np.random.default_rng(62)
    m = make_flat(4, np.full(4, 0x10000, dtype=np.uint32))
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSE_FIRSTN, 2, 0),
                           (cmap_mod.RULE_EMIT,)]))
    with pytest.raises(ValueError):
        mapper_ref.crush_do_rule(m, 0, 1, 2, choose_args={
            -1: {"ids": None, "weight_set": [[1, 2]]}})
    with pytest.raises(ValueError):
        mapper_ref.crush_do_rule(m, 0, 1, 2, choose_args={
            -1: {"ids": [9, 9], "weight_set": None}})
    # None entries are legal everywhere
    assert mapper_ref.crush_do_rule(m, 0, 1, 2,
                                    choose_args={-1: None})
    from ceph_tpu import native
    try:
        native.lib()
    except Exception:
        pytest.skip("native lib unavailable")
    assert native.crush_do_rule_native(m, 0, 1, 2,
                                       choose_args={-1: None})
