"""Batched CRUSH mapping on TPU: all PGs in one device program.

The reference recomputes PG mappings with a pool of CPU threads walking
crush_do_rule one PG at a time (OSDMapMapping/ParallelPGMapper,
/root/reference/src/osd/OSDMapMapping.h:17-169). Here the whole sweep is
one jitted integer program: hashes, fixed-point ln, draws and argmaxes
vectorized over [batch, replica, bucket-item], bit-exact against
mapper.c (differential tests compile the reference C as the oracle).

Scope of the device fast path: straw2 hierarchies (the modern default
bucket type) with choose/chooseleaf in BOTH indep (EC pools) and
firstn (replicated pools) modes, for rules of the canonical
take -> choose(leaf) -> emit shape under the jewel tunables. Legacy
bucket algs, multi-step rules, exotic tunables, and malformed maps
fall back to the scalar interpreter (ceph_tpu.crush.mapper_ref),
which handles the full op set.

Int64 fixed-point math requires x64; the public entry points wrap traces
in jax.enable_x64() so the global flag stays untouched.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import hashing
from .ln import LN_MIN_OFFSET, crush_ln, straw2_draw_divide
from .map import (CRUSH_ITEM_NONE, CRUSH_ITEM_UNDEF, CrushMap,
                  RULE_CHOOSE_FIRSTN, RULE_CHOOSE_INDEP,
                  RULE_CHOOSELEAF_FIRSTN, RULE_CHOOSELEAF_INDEP, RULE_EMIT,
                  RULE_SET_CHOOSE_TRIES, RULE_SET_CHOOSELEAF_TRIES, RULE_TAKE)

S64_MIN = -(1 << 63)


@dataclass(frozen=True)
class CompiledMap:
    """Dense array form of a straw2 CrushMap for device execution."""
    items: np.ndarray      # [NB, S] int64, padded with 0
    weights: np.ndarray    # [NB, S] int64 (16.16), padded with 0
    size: np.ndarray       # [NB] int64
    btype: np.ndarray      # [NB] int64
    depth: int             # max descent depth (levels of buckets)
    max_devices: int
    # choose_args substitution (crush.h crush_choose_arg): hash ids
    # (position-independent) and positional weight-sets. Per-bucket
    # position clamping (mapper.c:309-310) is materialized into wsets
    # at compile time, so runtime only clamps to npos-1 globally.
    # Without choose_args: ids == items, wsets == weights[:, None].
    ids: np.ndarray = None     # [NB, S] int64 — values fed to the hash
    wsets: np.ndarray = None   # [NB, P, S] int64 — weights per position
    npos: int = 1              # P (max positions across buckets)


def compile_map(cmap: CrushMap, choose_args=None) -> CompiledMap:
    nb = cmap.max_buckets
    s = max(b.size for b in cmap.buckets.values())
    items = np.zeros((nb, s), dtype=np.int64)
    weights = np.zeros((nb, s), dtype=np.int64)
    size = np.zeros(nb, dtype=np.int64)
    btype = np.zeros(nb, dtype=np.int64)
    for bid, b in cmap.buckets.items():
        if b.alg != "straw2":
            raise NotImplementedError(
                "batched mapper requires straw2 buckets (got %r); use "
                "mapper_ref for legacy algs" % b.alg)
        idx = -1 - bid
        items[idx, :b.size] = b.items
        weights[idx, :b.size] = b.weights
        size[idx] = b.size
        btype[idx] = b.type
    ids = items.copy()
    npos = 1
    if choose_args:
        for bid, arg in choose_args.items():
            if arg and arg.get("weight_set"):
                npos = max(npos, len(arg["weight_set"]))
    wsets = np.repeat(weights[:, None, :], npos, axis=1)
    if choose_args:
        for bid, arg in choose_args.items():
            if not arg or bid not in cmap.buckets:
                continue
            idx = -1 - bid
            bsz = cmap.buckets[bid].size
            if arg.get("ids"):
                ids[idx, :bsz] = np.asarray(arg["ids"], dtype=np.int64)
            ws = arg.get("weight_set")
            if ws:
                for p, row in enumerate(ws):
                    wsets[idx, p, :bsz] = np.asarray(row,
                                                     dtype=np.int64)
                # positions past the bucket's own count clamp to its
                # last (mapper.c:309-310)
                for p in range(len(ws), npos):
                    wsets[idx, p, :bsz] = wsets[idx, len(ws) - 1, :bsz]

    def depth_of(bid, seen=frozenset()):
        if bid not in cmap.buckets:
            raise ValueError("dangling bucket reference %d" % bid)
        if bid in seen:
            raise ValueError("cycle through bucket %d" % bid)
        b = cmap.buckets[bid]
        kids = [int(i) for i in b.items if i < 0]
        if not kids:
            return 1
        return 1 + max(depth_of(k, seen | {bid}) for k in kids)

    depth = max(depth_of(bid) for bid in cmap.buckets)
    return CompiledMap(items=items, weights=weights, size=size, btype=btype,
                       depth=depth, max_devices=cmap.max_devices,
                       ids=ids, wsets=wsets, npos=npos)


def _straw2_choose(arrays, bucket_idx, x, r, pos, xp):
    """Vectorized bucket_straw2_choose (mapper.c:322-367) with
    choose_args substitution: the hash consumes the (possibly
    replaced) ids, the draw divides by the position's weight-set row.

    bucket_idx, x, r: [...] int64 arrays -> chosen item [...] int64.
    pos: int or [...] int64 — the weight-set position (outpos)."""
    cm_items, cm_ids, cm_wsets, cm_size, _ = arrays
    items = cm_items[bucket_idx]          # [..., S]
    ids = cm_ids[bucket_idx]              # [..., S]
    npos = cm_wsets.shape[1]
    if npos == 1:
        weights = cm_wsets[bucket_idx, 0]
    else:
        p_eff = xp.clip(xp.asarray(pos, dtype=xp.int64), 0, npos - 1)
        p_eff = xp.broadcast_to(p_eff, bucket_idx.shape)
        weights = cm_wsets[bucket_idx, p_eff]   # [..., S]
    size = cm_size[bucket_idx]            # [...]
    u = hashing.hash32_3(
        x[..., None].astype(xp.uint32),
        ids.astype(xp.uint32),
        r[..., None].astype(xp.uint32), xp=xp).astype(xp.int64) & 0xFFFF
    lnv = crush_ln(u, xp=xp) - LN_MIN_OFFSET
    draw = straw2_draw_divide(lnv, xp.maximum(weights, 1), xp)
    s_idx = xp.arange(items.shape[-1], dtype=xp.int64)
    valid = (s_idx < size[..., None]) & (weights > 0)
    draw = xp.where(valid, draw, S64_MIN)
    # C keeps the first maximum (strict >); argmax returns first occurrence
    high = xp.argmax(draw, axis=-1)
    return xp.take_along_axis(items, high[..., None], axis=-1)[..., 0]


def _is_out(weight_vec, item, x, max_devices, xp):
    """Vectorized is_out (mapper.c:407-421); item assumed >= 0."""
    idx = xp.clip(item, 0, len(weight_vec) - 1)
    w = weight_vec[idx]
    oob = item >= len(weight_vec)
    full = w >= 0x10000
    zero = w == 0
    h = hashing.hash32_2(x.astype(xp.uint32), item.astype(xp.uint32),
                         xp=xp).astype(xp.int64) & 0xFFFF
    probabilistic_in = h < w
    return oob | (~full & (zero | ~probabilistic_in))


def _descend(cm: CompiledMap, arrays, root_idx, x, r, target_type, xp,
             pos=0):
    """Walk from root until an item of target_type is chosen.

    pos: the weight-set position every straw2 draw in this descent
    uses (choose_args; the C passes the same outpos down the whole
    descent, mapper.c:512/722).

    Returns (item, ok, permanent): ok False on any failure; permanent True
    for the failures crush_choose_indep turns into CRUSH_ITEM_NONE without
    retrying (bad item id, wrong-type device, dangling bucket ref —
    mapper.c:724-751). Empty buckets and exhausted depth stay retryable
    (the C inner for(;;) just breaks, leaving the slot UNDEF)."""
    items_a, ids_a, wsets_a, size_a, btype_a = arrays
    nb = items_a.shape[0]
    root = xp.asarray(root_idx, dtype=xp.int64)
    # invalid roots (e.g. -1-item where item was a device) are clipped and
    # marked failed
    fail = (root < 0) | (root >= nb)
    cur = xp.broadcast_to(xp.clip(root, 0, nb - 1), x.shape).astype(xp.int64)
    fail = xp.broadcast_to(fail, x.shape)
    perm = xp.zeros(x.shape, dtype=bool)
    done = fail
    chosen = xp.zeros(x.shape, dtype=xp.int64)
    for _ in range(cm.depth):
        fail = fail | (~done & (size_a[cur] == 0))  # empty bucket: retryable
        done = done | fail
        item = _straw2_choose(arrays, cur, x, r, pos, xp)
        is_dev = item >= 0
        bad_dev = is_dev & (item >= cm.max_devices)
        bad_bucket = ~is_dev & ((-1 - item) >= nb)
        itype = xp.where(is_dev, 0, btype_a[xp.clip(-1 - item, 0, nb - 1)])
        hit = (itype == target_type) & ~bad_dev & ~bad_bucket
        newly_bad = ~done & ~hit & (is_dev | bad_dev | bad_bucket)
        perm = perm | newly_bad
        chosen = xp.where(~done & hit, item, chosen)
        fail = fail | newly_bad
        cur = xp.where(~done & ~hit & ~is_dev,
                       xp.clip(-1 - item, 0, nb - 1), cur)
        done = done | hit | fail
    fail = fail | ~done
    return chosen, ~fail, perm


def _make_indep(cm: CompiledMap, out_size: int, numrep: int,
                target_type: int, chooseleaf: bool, tries: int,
                recurse_tries: int):
    """Build the jitted indep kernel for static (map, rule) geometry.

    out_size slots are filled, but retry strides use the rule's full
    numrep (crush_do_rule clamps only the output count, mapper.c:1039-1046).
    """
    import jax
    import jax.numpy as jnp

    def run(items_a, ids_a, wsets_a, size_a, btype_a, xs, weight_vec,
            root_idx):
        arrays = (items_a, ids_a, wsets_a, size_a, btype_a)
        b = xs.shape[0]
        undef = jnp.int64(CRUSH_ITEM_UNDEF)
        none = jnp.int64(CRUSH_ITEM_NONE)
        out = jnp.full((b, out_size), undef)
        out2 = jnp.full((b, out_size), undef)
        reps = jnp.arange(out_size, dtype=jnp.int64)
        xsb = jnp.broadcast_to(xs[:, None], (b, out_size))

        def round_body(state):
            ftotal, out, out2 = state
            # Candidate selection is a pure function of (x, r), so the
            # hash/ln-heavy work runs vectorized over [B, R] in one pass;
            # only acceptance (the C rep loop's collision ordering) stays
            # sequential.
            rr = jnp.broadcast_to((reps + numrep * ftotal)[None, :],
                                  (b, out_size))
            # top-level indep descends use weight-set position 0 (the
            # C passes its starting outpos, mapper.c:719-723)
            item, ok0, perm = _descend(cm, arrays, root_idx, xsb, rr,
                                       target_type, jnp, pos=0)
            leaf = None
            if chooseleaf:
                # inner descent (crush_choose_indep recursion with left=1,
                # outpos=rep; mapper.c:767-786): r = rep + parent_r +
                # numrep * ftotal_inner; weight-set position = rep
                leaf = jnp.full((b, out_size), undef)
                pos_leaf = jnp.broadcast_to(reps[None, :], (b, out_size))
                for ft2 in range(recurse_tries):
                    r2 = rr + reps[None, :] + numrep * ft2
                    cand, lok, _ = _descend(cm, arrays, -1 - item, xsb, r2,
                                            0, jnp, pos=pos_leaf)
                    lok = lok & ~_is_out(weight_vec, cand, xsb,
                                         cm.max_devices, jnp)
                    take = (leaf == undef) & lok
                    leaf = jnp.where(take, cand, leaf)
                ok0 = ok0 & (leaf != undef)
            elif target_type == 0:
                ok0 = ok0 & ~_is_out(weight_vec, item, xsb,
                                     cm.max_devices, jnp)

            def rep_body(rep, carry):
                out, out2 = carry
                need = out[:, rep] == undef
                cand = item[:, rep]
                collide = jnp.any(out == cand[:, None], axis=1)
                ok = ok0[:, rep] & ~collide & need
                # permanent failures become NONE and stop retrying
                # (mapper.c:724-751)
                make_none = need & perm[:, rep]
                if chooseleaf:
                    out2 = out2.at[:, rep].set(
                        jnp.where(ok, leaf[:, rep],
                                  jnp.where(make_none, none, out2[:, rep])))
                out = out.at[:, rep].set(
                    jnp.where(ok, cand,
                              jnp.where(make_none, none, out[:, rep])))
                return out, out2

            out, out2 = jax.lax.fori_loop(0, out_size, rep_body, (out, out2))
            return ftotal + 1, out, out2

        def cond(state):
            ftotal, out, _ = state
            return (ftotal < tries) & jnp.any(out == undef)

        _, out, out2 = jax.lax.while_loop(cond, round_body, (0, out, out2))
        result = out2 if chooseleaf else out
        result = jnp.where(out == undef, jnp.int64(CRUSH_ITEM_NONE), result)
        return result

    from ..common.profiler import PROFILER
    return PROFILER.wrap_jit("crush.indep", jax.jit(run))


def _make_firstn(cm: CompiledMap, result_max: int, numrep: int,
                 target_type: int, chooseleaf: bool, tries: int,
                 recurse_tries: int, vary_r: int):
    """Jitted firstn kernel (crush_choose_firstn, mapper.c:443-560,
    under the jewel tunables the fast path gates on:
    choose_local_tries=0, choose_local_fallback_tries=0, stable=1).

    Candidate descents are pure functions of (x, rep, ftotal), so the
    hash-heavy work precomputes [B, numrep, tries] (+ [.., recurse]
    leaf candidates) in one vectorized pass; only the C loop's
    acceptance order — first-fit with collision against the accepted
    prefix, skip_rep on permanent failures — runs as a (cheap,
    batch-vectorized) sequential scan."""
    import jax
    import jax.numpy as jnp

    def run(items_a, ids_a, wsets_a, size_a, btype_a, xs, weight_vec,
            root_idx):
        arrays = (items_a, ids_a, wsets_a, size_a, btype_a)
        b = xs.shape[0]
        none = jnp.int64(CRUSH_ITEM_NONE)
        reps = jnp.arange(numrep, dtype=jnp.int64)
        fts = jnp.arange(tries, dtype=jnp.int64)
        # r = rep + parent_r(0) + ftotal (mapper.c:494-497)
        rr = jnp.broadcast_to(reps[None, :, None] + fts[None, None, :],
                              (b, numrep, tries))
        xb = jnp.broadcast_to(xs[:, None, None], (b, numrep, tries))
        # firstn's weight-set position is the LIVE outpos at acceptance
        # time (mapper.c:512), which the precompute can't know — so
        # candidates are computed per position (npos is small; without
        # choose_args there is exactly one) and the acceptance scan
        # selects the outpos'th variant.
        npos_eff = min(cm.npos, result_max) if cm.npos > 1 else 1

        def cands_at(p):
            item, ok, perm = _descend(cm, arrays, root_idx, xb, rr,
                                      target_type, jnp, pos=p)
            if chooseleaf:
                # inner recursion: numrep=1 (stable), parent_r = sub_r
                # (mapper.c:552-575), r_inner = sub_r + ftotal_inner;
                # the recursion inherits the caller's outpos => same p
                sub_r = rr if vary_r else jnp.zeros_like(rr)
                if vary_r > 1:
                    sub_r = rr >> (vary_r - 1)
                f2 = jnp.arange(recurse_tries, dtype=jnp.int64)
                r2 = sub_r[..., None] + f2[None, None, None, :]
                x2 = jnp.broadcast_to(xb[..., None],
                                      (b, numrep, tries, recurse_tries))
                leafcand, lok, lperm = _descend(
                    cm, arrays, -1 - item[..., None], x2, r2, 0, jnp,
                    pos=p)
                lok = lok & ~_is_out(weight_vec, leafcand, x2,
                                     cm.max_devices, jnp)
                return item, ok, perm, leafcand, lok, lperm
            if target_type == 0:
                okdev = ok & ~_is_out(weight_vec, item, xb,
                                      cm.max_devices, jnp)
            else:
                # bucket-emitting rule: is_out applies to devices only
                # (mapper.c:581-585 gates on itemtype == 0)
                okdev = ok
            return item, ok, perm, okdev

        # stack per-position candidate sets along a trailing axis
        per_pos = [cands_at(p) for p in range(npos_eff)]
        stacked = [jnp.stack(parts, axis=-1)
                   for parts in zip(*per_pos)]
        if chooseleaf:
            item_s, ok_s, perm_s, leafcand_s, lok_s, lperm_s = stacked
        else:
            item_s, ok_s, perm_s, okdev_s = stacked

        out = jnp.full((b, result_max), none)
        out2 = jnp.full((b, result_max), none)
        outpos = jnp.zeros((b,), dtype=jnp.int64)
        slots = jnp.arange(result_max, dtype=jnp.int64)

        def sel_pos(arr, outpos, extra_dims):
            """arr [B, ..., P] -> the outpos'th position variant."""
            if npos_eff == 1:
                return arr[..., 0]
            idx = jnp.clip(outpos, 0, npos_eff - 1)
            idx = idx.reshape((-1,) + (1,) * (extra_dims + 1))
            return jnp.take_along_axis(arr, idx, axis=-1)[..., 0]

        def rep_body(rep, carry):
            out, out2, outpos = carry
            cand = sel_pos(item_s[:, rep], outpos, 1)     # [B, T]
            # collision against the accepted prefix (it is fixed for
            # the duration of this rep's scan)
            collide = jnp.any(out[:, None, :] == cand[:, :, None],
                              axis=-1)           # [B, T]
            if chooseleaf:
                lc = sel_pos(leafcand_s[:, rep], outpos, 2)  # [B,T,T2]
                lcollide = jnp.any(
                    out2[:, None, None, :] == lc[..., None], axis=-1)
                lacc = sel_pos(lok_s[:, rep], outpos, 2) & ~lcollide
                lbad = sel_pos(lperm_s[:, rep], outpos, 2)
                first_lacc = jnp.argmax(lacc, axis=-1)
                any_lacc = jnp.any(lacc, axis=-1)
                first_lbad = jnp.where(
                    jnp.any(lbad, axis=-1),
                    jnp.argmax(lbad, axis=-1),
                    jnp.int64(recurse_tries))
                leaf_found = any_lacc & (first_lacc < first_lbad)
                leaf_pick = jnp.take_along_axis(
                    lc, first_lacc[..., None], axis=-1)[..., 0]
                acceptable = sel_pos(ok_s[:, rep], outpos, 1) \
                    & ~collide & leaf_found
            else:
                acceptable = sel_pos(okdev_s[:, rep], outpos, 1) \
                    & ~collide
            bad = sel_pos(perm_s[:, rep], outpos, 1)
            first_acc = jnp.argmax(acceptable, axis=-1)
            any_acc = jnp.any(acceptable, axis=-1)
            first_bad = jnp.where(jnp.any(bad, axis=-1),
                                  jnp.argmax(bad, axis=-1),
                                  jnp.int64(tries))
            accept = any_acc & (first_acc < first_bad) & \
                (outpos < result_max)
            pick = jnp.take_along_axis(cand, first_acc[:, None],
                                       axis=-1)[:, 0]
            at = slots[None, :] == outpos[:, None]
            sel = at & accept[:, None]
            out = jnp.where(sel, pick[:, None], out)
            if chooseleaf:
                lp = jnp.take_along_axis(leaf_pick,
                                         first_acc[:, None],
                                         axis=-1)[:, 0]
                out2 = jnp.where(sel, lp[:, None], out2)
            outpos = outpos + accept.astype(jnp.int64)
            return out, out2, outpos

        out, out2, outpos = jax.lax.fori_loop(
            0, numrep, rep_body, (out, out2, outpos))
        return out2 if chooseleaf else out

    from ..common.profiler import PROFILER
    return PROFILER.wrap_jit("crush.firstn", jax.jit(run))


_KERNEL_CACHE: dict = {}


def _indep_kernel(cm: CompiledMap, out_size, numrep, target_type, chooseleaf,
                  tries, recurse_tries, placement=None):
    key = ("indep", cm.items.tobytes(), cm.ids.tobytes(),
           cm.wsets.tobytes(), cm.npos,
           cm.size.tobytes(), cm.btype.tobytes(), cm.depth, cm.max_devices,
           out_size, numrep, target_type, chooseleaf, tries, recurse_tries,
           placement)
    kernel = _KERNEL_CACHE.get(key)
    if kernel is None:
        kernel = _make_indep(cm, out_size, numrep, target_type, chooseleaf,
                             tries, recurse_tries)
        if len(_KERNEL_CACHE) > 64:
            _KERNEL_CACHE.clear()
        _KERNEL_CACHE[key] = kernel
    return kernel


def _firstn_kernel(cm: CompiledMap, result_max, numrep, target_type,
                   chooseleaf, tries, recurse_tries, vary_r,
                   placement=None):
    key = ("firstn", cm.items.tobytes(), cm.ids.tobytes(),
           cm.wsets.tobytes(), cm.npos,
           cm.size.tobytes(), cm.btype.tobytes(), cm.depth, cm.max_devices,
           result_max, numrep, target_type, chooseleaf, tries,
           recurse_tries, vary_r, placement)
    kernel = _KERNEL_CACHE.get(key)
    if kernel is None:
        kernel = _make_firstn(cm, result_max, numrep, target_type,
                              chooseleaf, tries, recurse_tries, vary_r)
        if len(_KERNEL_CACHE) > 64:
            _KERNEL_CACHE.clear()
        _KERNEL_CACHE[key] = kernel
    return kernel


def _rule_shape(cmap: CrushMap, ruleno: int):
    """Extract (root, op, numrep_arg, type) from a canonical 3-step rule;
    None if the rule is outside the batched fast path."""
    steps = [s for s in cmap.rules[ruleno].steps]
    choose_tries = None
    leaf_tries = None
    core = []
    for s in steps:
        if s[0] == RULE_SET_CHOOSE_TRIES:
            choose_tries = s[1]
        elif s[0] == RULE_SET_CHOOSELEAF_TRIES:
            leaf_tries = s[1]
        else:
            core.append(s)
    if len(core) != 3 or core[0][0] != RULE_TAKE or core[2][0] != RULE_EMIT:
        return None
    op = core[1][0]
    if op not in (RULE_CHOOSE_INDEP, RULE_CHOOSELEAF_INDEP,
                  RULE_CHOOSE_FIRSTN, RULE_CHOOSELEAF_FIRSTN):
        return None
    return dict(root=core[0][1], op=op, numrep_arg=core[1][1],
                type=core[1][2], choose_tries=choose_tries,
                leaf_tries=leaf_tries)


def batched_do_rule(cmap: CrushMap, ruleno: int, xs, result_max: int,
                    weight=None, xs_sharding=None, choose_args=None,
                    device_out: bool = False, tables_sharding=None):
    """Map a whole batch of inputs in one device program.

    xs: [B] int array of crush inputs (pg seeds). Returns [B, result_max]
    int64 (CRUSH_ITEM_NONE marks holes). Falls back to the scalar
    interpreter when the rule/map is outside the fast path.

    device_out: return the device array WITHOUT the device->host copy
    (the caller pulls results when it wants them — benchmarks time the
    device sweep itself).

    choose_args: weight-set/ids substitution — an arg map dict
    (bucket_id -> {"ids", "weight_set"}) or an int selecting one of
    cmap.choose_args' sets (with default fallback).

    xs_sharding: optional jax sharding for the seed batch — a
    NamedSharding over a device mesh partitions the whole mapping sweep
    across chips (each seed's placement is independent, so no
    collectives are inserted).

    tables_sharding: optional sharding for the compiled CRUSH tables
    and weight vector — `NamedSharding(mesh, P())` replicates them to
    every mesh device (the SNIPPETS [1]-[3] sharded-data/replicated-
    params split), so each chip maps its seed shard against a local
    table copy.  `mesh_do_rule` is the convenience wrapper.
    """
    import jax
    import jax.numpy as jnp

    shape = _rule_shape(cmap, ruleno)
    # a device-resident seed array stays on device: np.asarray would
    # silently d2h it — the device path consumes it directly
    xs_is_dev = type(xs).__module__.startswith("jax")
    if not xs_is_dev:
        xs = np.asarray(xs)
    if isinstance(choose_args, int):
        choose_args = cmap.choose_args_get_with_fallback(choose_args)

    def scalar_fallback():
        # host path: a device seed array is pulled once (device_out
        # callers still receive a host array here — the fast path was
        # unavailable, so there is nothing device-resident to return)
        from .mapper_ref import crush_do_rule
        xs_host = np.asarray(xs)
        out = np.full((len(xs_host), result_max), CRUSH_ITEM_NONE,
                      dtype=np.int64)
        for i, x in enumerate(xs_host):
            res = crush_do_rule(cmap, ruleno, int(x), result_max, weight,
                                choose_args=choose_args)
            out[i, :len(res)] = res
        return out

    t = cmap.tunables
    firstn = shape is not None and shape["op"] in (
        RULE_CHOOSE_FIRSTN, RULE_CHOOSELEAF_FIRSTN)
    # the firstn kernel bakes in the jewel defaults it is bit-exact
    # for; exotic tunables ride the scalar interpreter
    firstn_ok = (firstn and t.choose_local_tries == 0
                 and t.choose_local_fallback_tries == 0
                 and t.chooseleaf_stable == 1)
    if (shape is None
            or (firstn and not firstn_ok)
            or (shape["op"] in (RULE_CHOOSELEAF_INDEP,
                                RULE_CHOOSELEAF_FIRSTN)
                and shape["type"] == 0)
            or any(b.alg != "straw2" for b in cmap.buckets.values())):
        return scalar_fallback()

    try:
        cm = compile_map(cmap, choose_args)
    except ValueError:
        # malformed map (dangling refs, cycles): scalar interpreter
        # degrades per-slot instead of failing the whole sweep
        return scalar_fallback()
    numrep = shape["numrep_arg"]
    if numrep <= 0:
        numrep += result_max
    out_size = min(numrep, result_max)
    tries = shape["choose_tries"] or (t.choose_total_tries + 1)
    chooseleaf = shape["op"] in (RULE_CHOOSELEAF_INDEP,
                                 RULE_CHOOSELEAF_FIRSTN)
    if weight is None:
        weight = np.full(cm.max_devices, 0x10000, dtype=np.int64)

    # compiled kernels are cached per placement as well as geometry: a
    # mesh-sharded sweep must not be served (or counted) as the
    # single-device sweep's compile-cache entry
    placement = None
    if xs_sharding is not None or tables_sharding is not None:
        placement = (repr(xs_sharding), repr(tables_sharding))
    if firstn:
        # recurse_tries per do_rule (mapper.c:1014-1020):
        # choose_leaf_tries, else 1 under chooseleaf_descend_once,
        # else choose_tries
        if shape["leaf_tries"]:
            recurse_tries = shape["leaf_tries"]
        elif t.chooseleaf_descend_once:
            recurse_tries = 1
        else:
            recurse_tries = tries
        kernel = _firstn_kernel(cm, result_max, numrep, shape["type"],
                                chooseleaf, tries, recurse_tries,
                                t.chooseleaf_vary_r, placement)
    else:
        recurse_tries = shape["leaf_tries"] or 1
        kernel = _indep_kernel(cm, out_size, numrep, shape["type"],
                               chooseleaf, tries, recurse_tries,
                               placement)
    with jax.enable_x64(True):
        xs_dev = jnp.asarray(xs, dtype=jnp.int64)
        if xs_sharding is not None:
            xs_dev = jax.device_put(xs_dev, xs_sharding)
        tables = (jnp.asarray(cm.items), jnp.asarray(cm.ids),
                  jnp.asarray(cm.wsets),
                  jnp.asarray(cm.size), jnp.asarray(cm.btype))
        wvec = jnp.asarray(weight, dtype=jnp.int64)
        if tables_sharding is not None:
            # replicate the CRUSH tables to every mesh device up front
            # (P() = no partitioning): each chip draws against a local
            # copy instead of GSPMD re-deciding placement per call
            tables = tuple(jax.device_put(tb, tables_sharding)
                           for tb in tables)
            wvec = jax.device_put(wvec, tables_sharding)
        out = kernel(*tables, xs_dev, wvec, -1 - shape["root"])
    if device_out:
        if out.shape[1] < result_max:
            with jax.enable_x64(True):
                out = jnp.pad(out,
                              ((0, 0), (0, result_max - out.shape[1])),
                              constant_values=CRUSH_ITEM_NONE)
        return out
    res = np.asarray(out)
    if res.shape[1] < result_max:
        pad = np.full((len(xs), result_max - res.shape[1]), CRUSH_ITEM_NONE,
                      dtype=np.int64)
        res = np.concatenate([res, pad], axis=1)
    return res


def make_batch_mesh(n_devices: int | None = None):
    """Flat 1-axis ('batch',) mesh over the first n local devices —
    the cluster-sweep shape (one PG shard per chip), as opposed to
    parallel.mesh.make_mesh's 2D codec mesh."""
    import jax
    from jax.sharding import Mesh
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    return Mesh(np.array(devices[:n_devices]), ("batch",))


def mesh_do_rule(cmap: CrushMap, ruleno: int, xs, result_max: int,
                 weight=None, mesh=None, choose_args=None):
    """Mesh-sharded bulk mapping: the PG seed batch partitions along a
    flat ('batch',) mesh axis while the compiled CRUSH tables (and the
    reweight vector) replicate to every chip — the sharded-data /
    replicated-params split of SNIPPETS [1]-[3].  Each seed maps
    independently, so no collectives are inserted and the result is
    bit-identical to batched_do_rule on one device (the balancer's
    native-oracle parity gate rides on this).

    Seeds are padded (by repeating the last seed) up to a multiple of
    the mesh size — NamedSharding needs an even split — and the pad
    rows are trimmed from the result.

    With the rateless work queue up (parallel/rateless.py, ROADMAP
    direction J) and no explicit mesh, the sweep rides the queue
    instead of fixed NamedSharding shards: seed micro-batches are
    pulled by idle devices, so a slow chip takes fewer seeds instead
    of gating the whole sweep.  Each seed still maps independently
    through the same compiled kernel, so the result stays
    bit-identical to the fixed-shard (and scalar-oracle) path.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        from ..parallel import rateless as _rl
        disp = _rl.get_dispatcher()
        xs_arr = np.asarray(xs)
        if disp is not None and len(xs_arr) > 1:
            return disp.map_batch(
                lambda sub: batched_do_rule(
                    cmap, ruleno, sub, result_max, weight,
                    choose_args=choose_args),
                xs_arr)
        mesh = make_batch_mesh()
    if len(mesh.axis_names) != 1:
        raise ValueError("mesh_do_rule wants a flat 1-axis mesh, got "
                         "axes %r" % (mesh.axis_names,))
    axis = mesh.axis_names[0]
    n_shards = int(mesh.devices.size)
    xs = np.asarray(xs)
    n = len(xs)
    if n == 0 or n_shards <= 1:
        return batched_do_rule(cmap, ruleno, xs, result_max, weight,
                               choose_args=choose_args)
    pad = (-n) % n_shards
    if pad:
        xs = np.concatenate([xs, np.repeat(xs[-1:], pad)])
    out = batched_do_rule(
        cmap, ruleno, xs, result_max, weight,
        xs_sharding=NamedSharding(mesh, P(axis)),
        choose_args=choose_args,
        tables_sharding=NamedSharding(mesh, P()))
    return out[:n]
