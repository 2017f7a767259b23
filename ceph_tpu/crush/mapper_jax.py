"""TPU-vectorized CRUSH placement: map a batch of inputs in one device call.

The reference's bulk placement simulation is a scalar x-loop —
``crushtool --test`` calls ``crush_do_rule`` once per input
(reference:src/crush/CrushTester.cc:648, mapper reference:src/crush/
mapper.c:854).  Here the whole batch of x values is one tensor program:
rjenkins hashing (integer adds/xors/shifts), the straw2 fixed-point-ln
draw (reference:mapper.c:302, ln tables reference:src/crush/
crush_ln_table.h), weight rejection (reference:mapper.c:385), and the
firstn/indep retry loops (reference:mapper.c:421,:612) all run as masked
vector ops over ``[X]`` lanes on the VPU.

Bit-exactness contract: for supported maps the output equals
:func:`ceph_tpu.crush.mapper.crush_do_rule` for every x
(tests/test_crush_vec.py checks this exhaustively).

Supported shape (the dev/bench topology — ``CrushMap.flat``):
- single-level rule: TAKE <straw2 bucket of devices> + CHOOSE_FIRSTN/
  CHOOSE_INDEP type 0 + EMIT;
- tunables with ``choose_local_tries == 0`` and
  ``choose_local_fallback_tries == 0`` (bobtail and every later profile);
  the legacy locals/fallback retries depend on stateful
  ``bucket_perm_choose`` scratch, which has no batched equivalent —
  ``supports()`` reports False and callers fall back to the scalar
  mapper.

int64 note: straw2 draws are signed-64 fixed point; ``crush_ln``'s
``(x * rh) >> 48`` would need 65 bits, so it is computed as a 24/24-bit
split multiply — exact in int64, no x64-only uint64 tricks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ln_tables
from .map import (
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP,
    CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
    CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_STABLE,
    CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_VARY_R,
    CRUSH_RULE_TAKE,
    CrushMap,
)

_SEED = 1315423911  # CRUSH_HASH_SEED
_S64_MIN_PY = -(1 << 63)


@functools.lru_cache(maxsize=1)
def _ln_tables_dev():
    """int64 ln tables, created lazily under a scoped x64 context.

    The exact-draw path needs signed-64 fixed point; flipping
    ``jax_enable_x64`` globally at import time silently changed dtype
    behavior of unrelated JAX code in the process (advisor r1 finding) —
    so x64 is scoped to the exact kernels instead, and the hot approx
    path stays 32-bit/f32 and needs no x64 at all."""
    with jax.enable_x64():
        return (
            jnp.asarray(np.array(ln_tables.RH_LH_TBL, dtype=np.int64)),
            jnp.asarray(np.array(ln_tables.LL_TBL, dtype=np.int64)),
        )

# SET_* steps that are no-ops for a flat (non-chooseleaf) rule
_LEAF_ONLY_SET_OPS = (
    CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_VARY_R,
    CRUSH_RULE_SET_CHOOSELEAF_STABLE,
)


# -- batched integer primitives ---------------------------------------------


def _mix(a, b, c):
    """One crush_hashmix round on uint32 lanes (reference:hash.c:12)."""
    a = (a - b - c) ^ (c >> 13)
    b = (b - c - a) ^ (a << 8)
    c = (c - a - b) ^ (b >> 13)
    a = (a - b - c) ^ (c >> 12)
    b = (b - c - a) ^ (a << 16)
    c = (c - a - b) ^ (b >> 5)
    a = (a - b - c) ^ (c >> 3)
    b = (b - c - a) ^ (a << 10)
    c = (c - a - b) ^ (b >> 15)
    return a, b, c


def hash32_2(a, b):
    """Batched crush_hash32_2 (reference:hash.c:37)."""
    a = a.astype(jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    h = jnp.uint32(_SEED) ^ a ^ b
    x = jnp.uint32(231232)
    y = jnp.uint32(1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a, b, c):
    """Batched crush_hash32_3 (reference:hash.c:48)."""
    a = a.astype(jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    c = jnp.asarray(c, jnp.uint32)
    h = jnp.uint32(_SEED) ^ a ^ b ^ c
    x = jnp.uint32(231232)
    y = jnp.uint32(1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def _bit_length_16(x):
    """bit_length for 0 < x < 2^17, branchless (5 halvings)."""
    n = jnp.zeros_like(x)
    for shift in (16, 8, 4, 2, 1):
        big = x >= (1 << shift)
        n = jnp.where(big, n + shift, n)
        x = jnp.where(big, x >> shift, x)
    return n + 1  # x is now 1


def crush_ln(xin):
    """Batched fixed-point 2^44*log2(x+1) (reference:mapper.c:248).

    ``xin`` int64 lanes in [0, 0xffff].  Runs under a scoped x64 context
    (signed-64 fixed point); the hot approx path never calls this.
    """
    with jax.enable_x64():
        rh_lh, ll = _ln_tables_dev()
        x = jnp.asarray(xin, jnp.int64) + 1  # 1..0x10000
        norm = (x & 0x18000) == 0
        bits = jnp.where(norm, 16 - _bit_length_16(x), 0)
        x = x << bits
        iexpon = 15 - bits
        index1 = (x >> 8) << 1
        rh = jnp.take(rh_lh, index1 - 256)
        lh = jnp.take(rh_lh, index1 + 1 - 256)
        # (x * rh) >> 48 exactly, without 65-bit overflow: rh = hi*2^24+lo
        rh_hi = rh >> 24
        rh_lo = rh & 0xFFFFFF
        xl64 = (x * rh_hi + ((x * rh_lo) >> 24)) >> 24
        lh = lh + jnp.take(ll, xl64 & 0xFF)
        return (iexpon << 44) + (lh >> 4)


def straw2_choose(x, items, weights, r):
    """Batched exact straw2 selection (reference:mapper.c:302).

    x [X] uint32 lanes; items/weights [n] device ids and 16.16 weights;
    r scalar. Returns [X] chosen item ids (first-max tie-break).

    Exact but slow on TPU: the ln-table gathers serialize (~15ns/lane per
    item). The choose loops use :func:`straw2_choose_approx` instead and
    fall back to the scalar mapper on flagged lanes.
    """
    n = items.shape[0]

    with jax.enable_x64():
        s64_min = jnp.int64(_S64_MIN_PY)

        def draw_for(i):
            u = (hash32_3(x, items[i], r) & jnp.uint32(0xFFFF)).astype(
                jnp.int64
            )
            ln = crush_ln(u) - (1 << 48)
            # div64_s64 truncates toward zero; ln <= 0 so negate-divide
            return jnp.where(
                weights[i] > 0, -((-ln) // jnp.maximum(weights[i], 1)),
                s64_min,
            )

        def body(i, carry):
            high, high_draw = carry
            d = draw_for(i)
            better = d > high_draw
            return (
                jnp.where(better, items[i], high),
                jnp.where(better, d, high_draw),
            )

        init = (jnp.full_like(x, items[0], dtype=jnp.int32), draw_for(0))
        high, _ = jax.lax.fori_loop(1, n, body, init)
        return high


# -- gather-free approximate straw2 with exact-fallback flags ----------------
#
# The draw actually compared by the reference is
#   q(u, w) = (2^48 - crush_ln(u)) // w          (smaller q wins)
# crush_ln is a table-defined fixed-point log2, and table gathers are the
# one primitive TPUs do badly (no vector gather unit — XLA serializes to
# ~15ns/lane). But log2 itself is a single fast VPU op, so the kernel
# computes
#   qa(u, w) = (16 - log2(u+1)) * (2^44 / w)     in f32
# and an error budget EB_w >= max_u |qa(u,w) - q(u,w)| measured EXACTLY
# over all 65536 u values at build time (plus floor slop and an ulp
# margin for libm-vs-XLA log2 differences). A lane's winner is decided by
# qa; if the runner-up is within EB of the winner the lane is flagged and
# the caller recomputes that x with the exact scalar mapper. The flagged
# fraction is ~1e-4, so the hot path is pure hashes + float math — no
# tables, no int64 division.


def _host_q_exact(w: int) -> np.ndarray:
    """q(u, w) for all u (exact, host; vectorized — a per-weight scalar
    crush_ln loop cost seconds per distinct weight on big hierarchies)."""
    return ((1 << 48) - _np_ln_all()) // np.int64(w)


@functools.lru_cache(maxsize=1)
def _qa_kernel():
    """The jitted qa(u) kernel used ONLY for budget measurement — the
    same expression the runtime choose kernels compute.  ``u`` is a
    RUNTIME argument: closing over it as a constant let XLA constant-fold
    the log2 on the host evaluator (code-review r2: verified via HLO), so
    the measurement never touched the device's actual log2."""

    @jax.jit
    def qa(u, inv_w):
        t = jnp.float32(16.0) - jnp.log2(u + jnp.float32(1.0))
        return t * inv_w

    return qa


@functools.lru_cache(maxsize=1)
def _u_all_dev():
    return jnp.asarray(np.arange(0x10000, dtype=np.float32))


@functools.lru_cache(maxsize=4096)
def measured_error_budget(w: int) -> float:
    """|qa - q| bound for one weight, measured over every u WITH THE
    RUNTIME XLA KERNEL on the active backend (advisor r1: a numpy-libm
    measurement could under-bound a backend whose log2 rounds
    differently).  The margin on top of the measured max covers the
    quotient floor (+2) plus a cushion for fusion-context rounding
    differences between this standalone kernel and the fused choose
    kernels (1% + 16 ulp-scale slack — the bit-exact tests fail loudly
    if it is ever too thin)."""
    if w <= 0:
        return 0.0
    qa = np.asarray(
        _qa_kernel()(_u_all_dev(), jnp.float32((1 << 44) / w)),
        dtype=np.float64,
    )
    err = np.abs(qa - _host_q_exact(w).astype(np.float64))
    return float(err.max() * 1.01 + 2.0 + 16.0)


_error_budget = measured_error_budget  # flat-path call sites


def straw2_choose_approx(x, items, inv_weights, err_budgets, ebmax, r):
    """Batched approximate straw2: (winner_item, ambiguous_flag) per lane.

    inv_weights [n] f32 = 2^44/w (0 for zero-weight items, which never
    win); err_budgets [n] f32 per-item |qa-q| bounds; ebmax = their max.
    A lane is ambiguous when the runner-up draw is within the combined
    error budget of the winner — the caller must resolve it exactly.
    """
    n = items.shape[0]
    BIG = jnp.float32(3.0e38)

    def qa_for(i):
        u = (hash32_3(x, items[i], r) & jnp.uint32(0xFFFF)).astype(jnp.float32)
        t = jnp.float32(16.0) - jnp.log2(u + 1.0)
        return jnp.where(inv_weights[i] > 0, t * inv_weights[i], BIG)

    def body(i, carry):
        best_q, best_i, best_eb, second_q = carry
        q = qa_for(i)
        better = q < best_q  # strict: first index wins ties (flagged below)
        second_q = jnp.where(better, best_q, jnp.minimum(second_q, q))
        return (
            jnp.where(better, q, best_q),
            jnp.where(better, items[i], best_i),
            jnp.where(better, err_budgets[i], best_eb),
            second_q,
        )

    best_q = qa_for(0)
    init = (
        best_q,
        jnp.full_like(x, items[0], dtype=jnp.int32),
        jnp.full_like(best_q, err_budgets[0]),
        jnp.full_like(best_q, BIG),
    )
    best_q, best_i, best_eb, second_q = jax.lax.fori_loop(1, n, body, init)
    # exact ties (==) and the all-zero-weight case land here too, since
    # then second_q - best_q == 0 <= budget
    ambiguous = (second_q - best_q) <= (best_eb + ebmax)
    return best_i, ambiguous


def is_out(x, weight, item):
    """Batched probabilistic rejection (reference:mapper.c:385).

    weight [max_devices] int32; item [X] device ids.
    """
    w = jnp.take(weight, item)
    hashed = (hash32_2(x, item) & jnp.uint32(0xFFFF)).astype(jnp.int32)
    return jnp.where(w >= 0x10000, False, jnp.where(w == 0, True, hashed >= w))


# -- choose loops ------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("numrep", "out_size", "tries"))
def choose_firstn(
    x, items, inv_weights, err_budgets, ebmax, reweight,
    numrep: int, out_size: int, tries: int,
):
    """Batched flat firstn (reference:mapper.c:421 with modern tunables:
    every failure re-descends with r = rep + ftotal).

    Returns ([X, out_size] device ids with CRUSH_ITEM_NONE in unfilled
    tail slots, [X] ambiguity flags). Flagged lanes may be wrong and must
    be recomputed exactly by the caller.
    """
    X = x.shape[0]
    width = min(numrep, out_size)
    lanes = jnp.arange(X)

    def rep_body(rep, carry):
        out, outpos, ambiguous = carry

        def cond(state):
            ftotal, active, _item, _amb = state
            return jnp.logical_and(ftotal < tries, active.any())

        def body(state):
            ftotal, active, item, amb = state
            r = rep + ftotal
            cand, amb_step = straw2_choose_approx(
                x, items, inv_weights, err_budgets, ebmax, r
            )
            amb = amb | (active & amb_step)
            collide = (out == cand[:, None]).any(axis=1)
            reject = is_out(x, reweight, cand)
            ok = active & ~collide & ~reject
            item = jnp.where(ok, cand, item)
            active = active & ~ok
            return ftotal + 1, active, item, amb

        state = (
            jnp.int32(0),
            outpos < width,  # lanes already full skip this rep (count==0)
            jnp.full((X,), CRUSH_ITEM_NONE, dtype=jnp.int32),
            ambiguous,
        )
        _ftotal, still_active, item, ambiguous = jax.lax.while_loop(
            cond, body, state
        )
        accepted = (outpos < width) & ~still_active
        slot = jnp.minimum(outpos, width - 1)
        slot_val = jnp.where(accepted, item, out[lanes, slot])
        out = out.at[lanes, slot].set(slot_val)
        outpos = outpos + accepted.astype(jnp.int32)
        return out, outpos, ambiguous

    out, _outpos, ambiguous = jax.lax.fori_loop(
        0, numrep, rep_body,
        (
            jnp.full((X, width), CRUSH_ITEM_NONE, dtype=jnp.int32),
            jnp.zeros((X,), dtype=jnp.int32),
            jnp.zeros((X,), dtype=bool),
        ),
    )
    return out, ambiguous


@functools.partial(jax.jit, static_argnames=("numrep", "out_size", "tries"))
def choose_indep(
    x, items, inv_weights, err_budgets, ebmax, reweight,
    numrep: int, out_size: int, tries: int,
):
    """Batched flat indep (reference:mapper.c:612): positionally stable,
    r = rep + numrep*ftotal (numrep = the rule's replica count even when
    out_size is clamped by result_max), holes stay CRUSH_ITEM_NONE.

    Returns ([X, out_size] ids, [X] ambiguity flags)."""
    X = x.shape[0]
    out = jnp.full((X, out_size), CRUSH_ITEM_NONE, dtype=jnp.int32)
    filled = jnp.zeros((X, out_size), dtype=bool)
    ambiguous = jnp.zeros((X,), dtype=bool)
    col_iota = jnp.arange(out_size)

    def cond(state):
        ftotal, out, filled, _amb = state
        return jnp.logical_and(ftotal < tries, ~filled.all())

    def body(state):
        ftotal, out, filled, amb = state

        def rep_body(rep, inner):
            # same-round earlier picks are visible to later positions
            out, filled, amb = inner
            r = rep + numrep * ftotal
            cand, amb_step = straw2_choose_approx(
                x, items, inv_weights, err_budgets, ebmax, r
            )
            colmask = col_iota == rep  # one-hot column select
            need = ~(filled & colmask[None, :]).any(axis=1)  # slot unfilled
            amb = amb | (need & amb_step)
            collide = (out == cand[:, None]).any(axis=1)
            reject = is_out(x, reweight, cand)
            ok = need & ~collide & ~reject
            write = ok[:, None] & colmask[None, :]
            out = jnp.where(write, cand[:, None], out)
            filled = filled | write
            return out, filled, amb

        out, filled, amb = jax.lax.fori_loop(
            0, out_size, rep_body, (out, filled, amb)
        )
        return ftotal + 1, out, filled, amb

    _ftotal, out, _filled, ambiguous = jax.lax.while_loop(
        cond, body, (jnp.int32(0), out, filled, ambiguous)
    )
    return out, ambiguous


# -- numpy exact engine (for ambiguous-lane resolution) ----------------------
#
# The flagged lanes (~1e-2..1e-3 of the batch) need the table-exact draw.
# Host numpy has real vector gathers, so the exact math runs here over
# just the flagged subset — same masked-batch semantics as the device
# kernels, values per the scalar oracle.

_RH_LH_NP = np.array(ln_tables.RH_LH_TBL, dtype=np.int64)
_LL_NP = np.array(ln_tables.LL_TBL, dtype=np.int64)


def _np_crush_ln(u: np.ndarray) -> np.ndarray:
    """Vectorized exact crush_ln over int64 lanes (reference:mapper.c:248)."""
    x = (u + 1).astype(np.int64)
    n = np.zeros_like(x)
    xx = x.copy()
    for shift in (16, 8, 4, 2, 1):
        big = xx >= (1 << shift)
        n[big] += shift
        xx[big] >>= shift
    bitlen = n + 1
    norm = (x & 0x18000) == 0
    bits = np.where(norm, 16 - bitlen, 0)
    x = x << bits
    iexpon = 15 - bits
    index1 = (x >> 8) << 1
    rh = _RH_LH_NP[index1 - 256]
    lh = _RH_LH_NP[index1 + 1 - 256]
    rh_hi, rh_lo = rh >> 24, rh & 0xFFFFFF
    xl64 = (x * rh_hi + ((x * rh_lo) >> 24)) >> 24
    lh = lh + _LL_NP[xl64 & 0xFF]
    return (iexpon.astype(np.int64) << 44) + (lh >> 4)


def _np_hash3(a, b, c):
    from .hashes import crush_hash32_3

    return crush_hash32_3(
        a.astype(np.uint32), np.uint32(b), np.uint32(c)
    )


@functools.lru_cache(maxsize=1)
def _np_ln_all() -> np.ndarray:
    return _np_crush_ln(np.arange(0x10000, dtype=np.int64))


@functools.lru_cache(maxsize=128)
def _np_draw_table(w: int) -> np.ndarray:
    """draw(u) for all 65536 u at one weight — one fancy-index per item
    replaces the whole ln+divide pipeline on the fallback path."""
    if w <= 0:
        return np.full(0x10000, -(1 << 63), dtype=np.int64)
    ln = _np_ln_all() - (1 << 48)
    return -((-ln) // np.int64(w))


def _np_straw2(xs, items, draw_tabs, r):
    """Exact batched straw2 on host (reference:mapper.c:302)."""
    best = np.full(xs.shape, items[0], dtype=np.int32)
    best_draw = None
    for item, tab in zip(items, draw_tabs):
        u = (_np_hash3(xs, item, r) & np.uint32(0xFFFF)).astype(np.int64)
        draw = tab[u]
        if best_draw is None:
            best_draw = draw
        else:
            better = draw > best_draw
            best = np.where(better, np.int32(item), best)
            best_draw = np.where(better, draw, best_draw)
    return best


def _np_is_out(xs, reweight, item):
    from .hashes import crush_hash32_2

    w = reweight[item]
    hashed = (
        crush_hash32_2(xs.astype(np.uint32), item.astype(np.uint32))
        & np.uint32(0xFFFF)
    ).astype(np.int32)
    return np.where(w >= 0x10000, False, np.where(w == 0, True, hashed >= w))


def np_choose_firstn(xs, items, weights, reweight, numrep, out_size, tries):
    """Host-exact counterpart of :func:`choose_firstn` (same semantics);
    retry rounds compress to the still-active lane subset."""
    X = len(xs)
    width = min(numrep, out_size)
    out = np.full((X, width), CRUSH_ITEM_NONE, dtype=np.int32)
    outpos = np.zeros(X, dtype=np.int32)
    lanes = np.arange(X)
    draw_tabs = [_np_draw_table(int(w)) for w in weights]
    for rep in range(numrep):
        active_idx = lanes[outpos < width]
        item = np.full(X, CRUSH_ITEM_NONE, dtype=np.int32)
        ftotal = 0
        while ftotal < tries and active_idx.size:
            xs_a = xs[active_idx]
            cand = _np_straw2(xs_a, items, draw_tabs, rep + ftotal)
            collide = (out[active_idx] == cand[:, None]).any(axis=1)
            reject = _np_is_out(xs_a, reweight, cand)
            ok = ~collide & ~reject
            item[active_idx[ok]] = cand[ok]
            active_idx = active_idx[~ok]
            ftotal += 1
        accepted = item != CRUSH_ITEM_NONE
        slot = np.minimum(outpos, width - 1)
        out[lanes[accepted], slot[accepted]] = item[accepted]
        outpos += accepted.astype(np.int32)
    return out


def np_choose_indep(xs, items, weights, reweight, numrep, out_size, tries):
    """Host-exact counterpart of :func:`choose_indep` (same semantics);
    retry rounds compress to lanes that still have unfilled slots."""
    X = len(xs)
    out = np.full((X, out_size), CRUSH_ITEM_NONE, dtype=np.int32)
    filled = np.zeros((X, out_size), dtype=bool)
    lanes = np.arange(X)
    draw_tabs = [_np_draw_table(int(w)) for w in weights]
    ftotal = 0
    while ftotal < tries:
        active_idx = lanes[~filled.all(axis=1)]
        if not active_idx.size:
            break
        xs_a = xs[active_idx]
        for rep in range(out_size):
            need = ~filled[active_idx, rep]
            cand = _np_straw2(xs_a, items, draw_tabs, rep + numrep * ftotal)
            collide = (out[active_idx] == cand[:, None]).any(axis=1)
            reject = _np_is_out(xs_a, reweight, cand)
            ok = need & ~collide & ~reject
            ok_lanes = active_idx[ok]
            out[ok_lanes, rep] = cand[ok]
            filled[ok_lanes, rep] = True
        ftotal += 1
    return out


# -- rule interpreter over the batch -----------------------------------------


def supports(cmap: CrushMap, ruleno: int) -> bool:
    """True if vec_do_rule handles this (map, rule) bit-exactly — either
    the flat fast path here or the hierarchical engine
    (mapper_jax_hier.py, chooseleaf included)."""
    if _supports_flat(cmap, ruleno):
        return True
    from .mapper_jax_hier import supports_hier

    return supports_hier(cmap, ruleno)


def _supports_flat(cmap: CrushMap, ruleno: int) -> bool:
    """The single-level straw2 shape the flat kernels handle."""
    t = cmap.tunables
    if t.choose_local_tries != 0 or t.choose_local_fallback_tries != 0:
        return False
    if ruleno < 0 or ruleno >= len(cmap.rules) or cmap.rules[ruleno] is None:
        return False
    steps = cmap.rules[ruleno].steps
    stage = 0  # expect TAKE -> CHOOSE -> EMIT (SET_* tunable steps ok)
    take_bucket = None
    for s in steps:
        if s.op == CRUSH_RULE_SET_CHOOSE_TRIES or s.op in _LEAF_ONLY_SET_OPS:
            continue  # tries handled; chooseleaf knobs are no-ops here
        if s.op in (
            CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
            CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
        ):
            if s.arg1 > 0:
                return False  # would enable the perm-choose fallback paths
            continue
        if stage == 0 and s.op == CRUSH_RULE_TAKE:
            take_bucket = s.arg1
            stage = 1
        elif stage == 1 and s.op in (
            CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSE_INDEP
        ) and s.arg2 == 0:
            stage = 2
        elif stage == 2 and s.op == CRUSH_RULE_EMIT:
            stage = 3
        else:
            return False
    if stage != 3 or take_bucket is None:
        return False
    bucket = cmap.buckets.get(take_bucket)
    if bucket is None or bucket.alg != CRUSH_BUCKET_STRAW2:
        return False
    return all(i >= 0 for i in bucket.items)


def vec_rule_stats(
    cmap: CrushMap,
    ruleno: int,
    xs,
    result_max: int,
    weight=None,
) -> tuple[dict[int, int], int]:
    """Profiled entry over :func:`_vec_rule_stats` — every bulk-sim
    call reports into the kernel profiler (ops.profiler): wall time,
    jit-cache behavior keyed on the lane count, and batch shapes, so
    ``dump_kernel_profile`` sees the CRUSH engine next to the EC ones."""
    from ..ops.profiler import profiler

    xs_np = np.asarray(xs, dtype=np.uint32)
    counts: dict[int, int] = {}
    bad = 0
    with profiler().timed(
        "crush_vec_stats", (ruleno, xs_np.shape, result_max),
        nbytes=xs_np.size * 4, shape=xs_np.shape,
    ):
        for part in _x_chunks(xs_np):
            c, b = _vec_rule_stats(cmap, ruleno, part, result_max, weight)
            for item, n in c.items():
                counts[item] = counts.get(item, 0) + n
            bad += b
    return counts, bad


# lanes per device launch of a rule program: compiled for a v5e, the
# 1024-OSD EC(8+3) chooseleaf-indep program needs 37.5 GB of HBM at
# 2^20 lanes and 4.3 GB at 2^17 (the chip has 16 GB)
X_CHUNK = 1 << 17


def _x_chunks(xs_np: np.ndarray) -> list[np.ndarray]:
    """``xs`` in launches of at most :data:`X_CHUNK` lanes (every full
    chunk shares one compiled program)."""
    return [xs_np[i:i + X_CHUNK]
            for i in range(0, max(len(xs_np), 1), X_CHUNK)]


def _vec_rule_stats(
    cmap: CrushMap,
    ruleno: int,
    xs,
    result_max: int,
    weight=None,
) -> tuple[dict[int, int], int]:
    """Bulk-sim statistics computed ON DEVICE: ({item: count}, bad_mappings).

    The CrushTester path: for 10^6 x a full [X, W] host fetch would
    dwarf the compute, so placements are bincounted on device and only the counts + ambiguity flags come
    back; flagged lanes are re-run on the scalar oracle and the counts
    patched. Identical numbers to counting vec_do_rule's output."""
    from .mapper_jax_hier import supports_hier

    xs_np = np.asarray(xs, dtype=np.uint32)
    w_arr = weight if weight is not None else cmap.get_weights()
    if _supports_flat(cmap, ruleno):
        eng = _flat_engine(cmap, ruleno, xs_np, result_max, weight)
        if eng is None:
            return {}, 0
        out_dev, amb_dev, p = eng

        def exact_fn(sub_xs):
            np_fn = np_choose_firstn if p["firstn"] else np_choose_indep
            return np_fn(
                sub_xs, p["items"], p["item_ws"],
                np.array(w_arr, dtype=np.int32),
                int(p["numrep"]), int(p["out_size"]), int(p["tries"]),
            )
    elif supports_hier(cmap, ruleno):
        from .mapper_jax_hier import _hier_engine, np_do_rule_hier

        eng = _hier_engine(cmap, ruleno, xs_np, result_max, weight)
        if eng is None:
            return {}, 0
        out_dev, amb_dev = eng

        def exact_fn(sub_xs):
            return np_do_rule_hier(cmap, ruleno, sub_xs, result_max, weight)
    else:
        raise ValueError("map/rule shape not supported by the vectorized path")

    width = out_dev.shape[1]
    # item ids span [-max_buckets, max_devices): shift into bincount range
    offset = max(1, cmap.max_buckets)
    length = offset + cmap.max_devices
    flat = out_dev.ravel()
    mask = flat != CRUSH_ITEM_NONE
    counts_dev = jnp.bincount(
        jnp.where(mask, flat + offset, 0),
        weights=mask.astype(jnp.int32),
        length=length,
    )
    placed = (out_dev != CRUSH_ITEM_NONE).sum(axis=1)
    bad_dev = (placed < width).sum()
    counts = np.asarray(counts_dev).astype(np.int64)
    bad = int(bad_dev)
    amb = np.asarray(amb_dev)
    if amb.any():
        flagged = np.nonzero(amb)[0]
        rows = np.asarray(
            jnp.take(out_dev, jnp.asarray(flagged), axis=0)
        )  # small: only the flagged subset comes back to the host
        exact = exact_fn(xs_np[flagged].astype(np.uint32))
        for old, new in ((rows, -1), (exact, +1)):
            filled = old != CRUSH_ITEM_NONE
            vals, cnts = np.unique(old[filled], return_counts=True)
            for v, c in zip(vals, cnts):
                counts[int(v) + offset] += new * int(c)
            bad += new * int((filled.sum(axis=1) < width).sum())
    return (
        {int(i) - offset: int(c) for i, c in enumerate(counts) if c},
        bad,
    )


def _flat_engine(cmap, ruleno, xs_np, result_max, weight):
    """Run the flat choose kernels; (out_dev, amb_dev) or None (empty)."""
    rule = cmap.rules[ruleno]
    t = cmap.tunables
    tries = t.choose_total_tries + 1
    take_bucket = None
    numrep = result_max
    firstn = True
    for s in rule.steps:
        if s.op == CRUSH_RULE_TAKE:
            take_bucket = cmap.buckets[s.arg1]
        elif s.op == CRUSH_RULE_SET_CHOOSE_TRIES and s.arg1 > 0:
            tries = s.arg1
        elif s.op in (CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSE_INDEP):
            firstn = s.op == CRUSH_RULE_CHOOSE_FIRSTN
            numrep = s.arg1 if s.arg1 > 0 else s.arg1 + result_max
    if numrep <= 0:
        return None
    out_size = min(numrep, result_max)
    if weight is None:
        weight = cmap.get_weights()
    item_ws = list(take_bucket.item_weights)
    inv_w = np.array(
        [(1 << 44) / w if w > 0 else 0.0 for w in item_ws], dtype=np.float32
    )
    budgets = np.array(
        [_error_budget(w) if w > 0 else 0.0 for w in item_ws],
        dtype=np.float32,
    )
    ebmax = np.float32(budgets.max() if budgets.size else 0.0)
    fn = choose_firstn if firstn else choose_indep
    out_dev, amb_dev = fn(
        jnp.asarray(xs_np),
        jnp.asarray(np.array(take_bucket.items, dtype=np.int32)),
        jnp.asarray(inv_w),
        jnp.asarray(budgets),
        ebmax,
        jnp.asarray(np.array(weight, dtype=np.int32)),
        numrep=int(numrep), out_size=int(out_size), tries=int(tries),
    )
    params = {
        "firstn": firstn, "numrep": numrep, "out_size": out_size,
        "tries": tries, "items": list(take_bucket.items),
        "item_ws": item_ws,
    }
    return out_dev, amb_dev, params


def vec_do_rule(
    cmap: CrushMap,
    ruleno: int,
    xs,
    result_max: int,
    weight=None,
) -> np.ndarray:
    """Profiled entry over :func:`_vec_do_rule` (see vec_rule_stats)."""
    from ..ops.profiler import profiler

    xs_np = np.asarray(xs, dtype=np.uint32)
    with profiler().timed(
        "crush_vec_rule", (ruleno, xs_np.shape, result_max),
        nbytes=xs_np.size * 4, shape=xs_np.shape,
    ):
        return np.concatenate([
            _vec_do_rule(cmap, ruleno, part, result_max, weight)
            for part in _x_chunks(xs_np)
        ])


def _vec_do_rule(
    cmap: CrushMap,
    ruleno: int,
    xs,
    result_max: int,
    weight=None,
) -> np.ndarray:
    """Batched crush_do_rule over ``xs`` (reference:mapper.c:854 x-loop
    collapsed to one device program).

    Returns [X, numrep] int32 (CRUSH_ITEM_NONE holes); bit-identical to
    the scalar mapper for supported maps (check with :func:`supports`).
    Hierarchical maps (chooseleaf included) route to the multi-level
    engine in mapper_jax_hier.py.
    """
    if not _supports_flat(cmap, ruleno):
        from .mapper_jax_hier import supports_hier, vec_do_rule_hier

        if supports_hier(cmap, ruleno):
            return vec_do_rule_hier(cmap, ruleno, xs, result_max, weight)
        raise ValueError("map/rule shape not supported by the vectorized path")
    if weight is None:
        weight = cmap.get_weights()
    xs_np = np.asarray(xs, dtype=np.uint32)
    eng = _flat_engine(cmap, ruleno, xs_np, result_max, weight)
    if eng is None:
        return np.zeros((len(xs_np), 0), dtype=np.int32)
    out, ambiguous, p = eng
    out = np.array(out)  # writable host copy (fallback splices below)
    ambiguous = np.asarray(ambiguous)
    # exact-resolution fallback: lanes whose straw2 runner-up fell inside
    # the f32 error budget are recomputed with the exact table math —
    # batched numpy over just the flagged subset, so the cost stays
    # proportional to the (small) flagged fraction
    if ambiguous.any():
        flagged = np.nonzero(ambiguous)[0]
        np_fn = np_choose_firstn if p["firstn"] else np_choose_indep
        exact = np_fn(
            xs_np[flagged].astype(np.uint32),
            p["items"],
            p["item_ws"],
            np.array(weight, dtype=np.int32),
            int(p["numrep"]), int(p["out_size"]), int(p["tries"]),
        )
        out[flagged] = exact
    return out
