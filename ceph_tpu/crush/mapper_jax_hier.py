"""TPU-vectorized CRUSH for HIERARCHICAL maps (chooseleaf included).

Extends the flat batched mapper (mapper_jax.py) to multi-level straw2
hierarchies — the realistic hosts×racks maps whose bulk simulation is
the reference's actual target (reference:src/crush/mapper.c:421
crush_choose_firstn recursive descent + chooseleaf, :612
crush_choose_indep; rule interpreter :854).

Design
------
Per-map tables (padded [n_buckets, max_items]) let one device program
evaluate straw2 for a *different bucket per lane*: a ``jnp.take`` row
gather fetches each lane's item ids / inverse weights / child-row
indices, and the draw loop runs over the padded item axis.  The descent
from the TAKE root to the target type is a static loop bounded by the
map's depth; the firstn retry ladder (per-lane ftotal), the chooseleaf
inner recursion (single-rep firstn at type 0 with vary_r/stable
semantics), and indep's round-global retries are masked vector loops —
the exact control flow of the scalar mapper, one mask per branch.

Draws use the gather-free f32 approximation of mapper_jax (a TPU has no
fast vector gather for the 65536-entry ln table): each straw2 winner
whose runner-up falls inside a *measured-on-this-backend* error budget
flags its lane, and flagged lanes are recomputed with the exact scalar
mapper on the host.  Bit-exactness contract: for supported maps the
combined output equals ``crush_do_rule`` for every x
(tests/test_crush_vec.py hierarchy suite).

Supported shape (``supports_hier``):
- every bucket straw2; acyclic, bounded depth;
- one TAKE -> one CHOOSE[LEAF]_FIRSTN/INDEP -> EMIT (any target type);
- modern tunables (choose_local_tries == choose_local_fallback_tries
  == 0); chooseleaf_vary_r / chooseleaf_stable fully supported;
- CHAINED rules — TAKE -> CHOOSE_INDEP -> ... -> CHOOSE[LEAF]_INDEP ->
  EMIT, the LRC per-layer shape
  (reference:src/erasure-code/lrc/ErasureCodeLrc.cc:44) — run on
  device via ``_chain_engine``: each later step is one flattened
  [X*width] engine dispatch rooted at the previous step's buckets.
  Caveat: the f32 draw ambiguity compounds across a chain's many draws
  (~10-15% of lanes flagged vs <1% single-step), and flagged lanes
  recompute on the host through the batched exact numpy chain
  (``_np_chain``) — still bit-exact, but chains land ~10x over the
  scalar loop rather than the 300x of single-step shapes.  Only rules
  the shape parser rejects (firstn chains, mid-chain clamps) fall back
  to the scalar mapper, and CrushTester warns loudly when that happens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .map import (
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP,
    CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP,
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

_NONE = CRUSH_ITEM_NONE
_UNDEF = 0x7FFFFFFE  # CRUSH_ITEM_UNDEF
_BIG = 3.0e38

_CHOOSE_OPS = (
    CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP,
    CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP,
)


# -- per-map device tables ---------------------------------------------------


class MapTables:
    """Padded bucket tables for lane-varying straw2 (host-built, cached
    on the map object; invalidated by identity, so mutate-and-reuse maps
    should drop ``cmap._vec_hier_tables``)."""

    def __init__(self, cmap: CrushMap):
        from .mapper_jax import measured_error_budget

        bids = sorted(cmap.buckets)
        self.row_of = {bid: i for i, bid in enumerate(bids)}
        B = len(bids)
        I = max((len(cmap.buckets[b].items) for b in bids), default=1)
        items = np.full((B, I), float(_NONE), dtype=np.float32)
        invw = np.zeros((B, I), dtype=np.float32)
        eb = np.zeros((B, I), dtype=np.float32)
        childrow = np.full((B, I), -1, dtype=np.int32)
        size = np.zeros(B, dtype=np.int32)
        btype = np.zeros(B, dtype=np.int32)
        for bi, bid in enumerate(bids):
            b = cmap.buckets[bid]
            size[bi] = len(b.items)
            btype[bi] = b.type
            for ii, (it, w) in enumerate(zip(b.items, b.item_weights)):
                items[bi, ii] = float(it)
                if w > 0:
                    invw[bi, ii] = np.float32((1 << 44) / w)
                    eb[bi, ii] = measured_error_budget(int(w))
                if it < 0 and it in cmap.buckets:
                    childrow[bi, ii] = self.row_of[it]
        # child item type (0 for devices): lets the descent read the
        # chosen item's type from the same packed row fetch, no gather
        childtype = np.zeros((B, I), dtype=np.float32)
        for bi, bid in enumerate(bids):
            b = cmap.buckets[bid]
            for ii, it in enumerate(b.items):
                if it < 0 and it in cmap.buckets:
                    childtype[bi, ii] = float(cmap.buckets[it].type)
        self.I = I
        self.B = B
        # dense bucket-id -> table-row lookup (ids are negative: index
        # -1-id); -1 = not a bucket.  Lets a chained CHOOSE step resolve
        # the previous step's output ids to rows ON DEVICE.
        max_idx = max((-1 - bid for bid in bids), default=0)
        id2row = np.full(max_idx + 1, -1, dtype=np.int32)
        for bid in bids:
            id2row[-1 - bid] = self.row_of[bid]
        self.id2row = id2row
        self.depth = self._max_depth(cmap, bids)
        self.ebmax = float(eb.max()) if eb.size else 0.0
        # ONE packed [B, 5I+1] matrix: a single one-hot MXU matmul per
        # straw2 call fetches every per-lane bucket row (TPUs have no
        # fast vector gather; a take-based version measured 6.5s/1M x,
        # the matmul form is the fix). f32 is exact for ids < 2^24.
        self.packed = jnp.asarray(
            np.concatenate(
                [
                    items,
                    invw,
                    eb,
                    childrow.astype(np.float32),
                    childtype,
                    size.astype(np.float32)[:, None],
                ],
                axis=1,
            )
        )
        self.btype = jnp.asarray(btype)

    @staticmethod
    def _max_depth(cmap: CrushMap, bids) -> int:
        depth: dict[int, int] = {}

        def d(bid: int) -> int:
            if bid in depth:
                return depth[bid]
            depth[bid] = 0  # cycle guard (supports_hier rejects cycles)
            best = 0
            for it in cmap.buckets[bid].items:
                if it < 0 and it in cmap.buckets:
                    best = max(best, 1 + d(it))
            depth[bid] = best
            return best

        return max((d(b) for b in bids), default=0)

    def tree(self):
        return (self.packed,)


def tables_for(cmap: CrushMap) -> MapTables:
    t = getattr(cmap, "_vec_hier_tables", None)
    if t is None:
        t = MapTables(cmap)
        cmap._vec_hier_tables = t
    return t


# -- batched primitives ------------------------------------------------------


def _straw2_rows(T, x, rows, r, ebmax):
    """straw2 over a per-lane bucket:
    (item, child_row, child_type, ambiguous, empty).

    x [X] uint32; rows [X] int32 bucket-row indices; r [X] int32.

    The per-lane bucket row is fetched with ONE one-hot matmul against
    the packed [B, 5I+1] table — exact under Precision.HIGHEST (one-hot
    factors are 1.0/0.0, so the bf16x-pass products and zero sums
    reproduce each f32 entry bit-for-bit) and MXU-fast, where a
    take-gather version measured ~15ns/lane.
    """
    from .mapper_jax import hash32_3

    (packed,) = T
    B = packed.shape[0]
    I = (packed.shape[1] - 1) // 5
    rows = jnp.maximum(rows, 0)  # -1 sentinels ride under dead masks
    onehot = (
        rows[:, None] == jnp.arange(B, dtype=rows.dtype)[None, :]
    ).astype(jnp.float32)
    fetched = jnp.matmul(
        onehot, packed, precision=jax.lax.Precision.HIGHEST
    )  # [X, 5I+1]
    it_l = fetched[:, 0:I].T          # [I, X] f32 item ids
    iw_l = fetched[:, I : 2 * I].T    # inverse weights
    eb_l = fetched[:, 2 * I : 3 * I].T
    cr_l = fetched[:, 3 * I : 4 * I].T  # child row (f32-exact ints)
    ct_l = fetched[:, 4 * I : 5 * I].T  # child type (0 = device)
    empty = fetched[:, 5 * I] == 0

    # all I draws at once: [I, X] hashes + draws, then a first-min
    # argmin — one wide fused kernel instead of I loop-carried passes
    it_all = it_l.astype(jnp.int32)                       # [I, X]
    u = (
        hash32_3(x[None, :], it_all, r.astype(jnp.uint32)[None, :])
        & jnp.uint32(0xFFFF)
    ).astype(jnp.float32)
    q = jnp.where(
        iw_l > 0, (jnp.float32(16.0) - jnp.log2(u + 1.0)) * iw_l, _BIG
    )                                                     # [I, X]
    best = jnp.argmin(q, axis=0)                          # first-min wins
    sel = jnp.arange(I, dtype=best.dtype)[:, None] == best[None, :]
    bq = jnp.min(q, axis=0)
    second = jnp.min(jnp.where(sel, _BIG, q), axis=0)
    pick = lambda a: jnp.where(sel, a, 0).sum(axis=0)  # noqa: E731
    bit = pick(it_all)
    brow = pick(cr_l).astype(jnp.int32)
    btyp = pick(ct_l).astype(jnp.int32)
    beb = pick(eb_l)
    ambiguous = (second - bq) <= (beb + ebmax)
    return bit, brow, btyp, ambiguous, empty


def _descend(T, x, rows0, r, want_type, max_depth, ebmax):
    """Drill from per-lane root buckets to the first item of want_type
    (the retry_bucket descent of mapper.c:421/:612, minus empty/wrong-type
    handling which the callers mask).  Returns
    (item, item_row, resolved, dead, empty_hit, ambiguous)."""
    X = x.shape[0]
    cur = rows0
    item = jnp.full((X,), _NONE, dtype=jnp.int32)
    item_row = jnp.full((X,), -1, dtype=jnp.int32)
    resolved = jnp.zeros((X,), dtype=bool)
    dead = jnp.zeros((X,), dtype=bool)
    empty_hit = jnp.zeros((X,), dtype=bool)
    amb = jnp.zeros((X,), dtype=bool)
    for _d in range(max_depth + 1):
        it, crow, t, amb_d, empty = _straw2_rows(T, x, cur, r, ebmax)
        live = ~resolved & ~dead & ~empty_hit
        amb = amb | (live & amb_d)
        empty_hit = empty_hit | (live & empty)
        live = live & ~empty
        hit = live & (t == want_type)
        item = jnp.where(hit, it, item)
        item_row = jnp.where(hit, crow, item_row)
        resolved = resolved | hit
        godeep = live & ~hit & (it < 0) & (crow >= 0)
        dead = dead | (live & ~hit & ~godeep)
        cur = jnp.where(godeep, crow, cur)
    dead = dead | (~resolved & ~dead & ~empty_hit)  # depth exhausted
    return item, item_row, resolved, dead, empty_hit, amb


def _is_out_vec(x, reweight, item):
    from .mapper_jax import hash32_2

    n = reweight.shape[0]
    idx = jnp.clip(item, 0, n - 1)
    w = jnp.take(reweight, idx)
    w = jnp.where((item < 0) | (item >= n), 0, w)  # out-of-range: out
    hashed = (hash32_2(x, item.astype(jnp.uint32)) & jnp.uint32(0xFFFF)
              ).astype(jnp.int32)
    return jnp.where(w >= 0x10000, False, jnp.where(w == 0, True, hashed >= w))


def _collides(out, outpos, item):
    """item already in out[:, :outpos]? ([X,W], [X], [X]) -> [X] bool."""
    W = out.shape[1]
    cols = jnp.arange(W)[None, :]
    return ((out == item[:, None]) & (cols < outpos[:, None])).any(axis=1)


# -- chooseleaf inner recursion (single-rep firstn at type 0) ---------------


def _leaf_firstn(
    T, x, sub_rows, rep2, sub_r, out2, outpos, reweight,
    recurse_tries: int, max_depth: int, ebmax, want,
):
    """The recursive leaf step of crush_choose_firstn (mapper.c:995-1012
    via the python port): one rep (index rep2), parent_r=sub_r, descend
    to a device, collide against out2[:, :outpos], is_out rejection.
    Returns (leaf, ok, ambiguous) for lanes in ``want``."""
    X = x.shape[0]
    leaf = jnp.full((X,), _NONE, dtype=jnp.int32)
    done = jnp.zeros((X,), dtype=bool)
    failed = jnp.zeros((X,), dtype=bool)
    amb = jnp.zeros((X,), dtype=bool)
    ftotal = jnp.zeros((X,), dtype=jnp.int32)

    # static unroll: recurse_tries is 1 under modern tunables
    # (chooseleaf_descend_once), and a nested lax.while_loop inside the
    # outer retry loop compiled pathologically; per-lane ftotal is kept
    # so r2 matches the scalar ladder exactly
    for _t in range(recurse_tries):
        live = want & ~done & ~failed & (ftotal < recurse_tries)
        r2 = rep2 + sub_r + ftotal
        item, _row, resolved, dead, empty, amb_d = _descend(
            T, x, sub_rows, r2, 0, max_depth, ebmax
        )
        amb = amb | (live & amb_d)
        coll = _collides(out2, outpos, item)
        rej = resolved & (coll | _is_out_vec(x, reweight, item))
        ok_now = live & resolved & ~rej
        leaf = jnp.where(ok_now, item, leaf)
        done = done | ok_now
        # wrong-type terminal inside the leaf descent = inner skip_rep:
        # the inner rep is abandoned, the leaf fails for good
        failed = failed | (live & dead)
        retry = live & ~ok_now & ~dead
        ftotal = ftotal + retry.astype(jnp.int32)
    return leaf, done, amb


# -- firstn ------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "numrep", "width", "tries", "recurse_tries", "want_type", "leaf",
        "vary_r", "stable", "max_depth",
    ),
)
def choose_firstn_hier(
    tables, x, root_row, reweight, ebmax,
    numrep: int, width: int, tries: int, recurse_tries: int,
    want_type: int, leaf: bool, vary_r: int, stable: int, max_depth: int,
):
    """Batched crush_choose_firstn over a hierarchy (mapper.c:421).

    Returns (out [X,width], out2 [X,width], outpos [X], ambiguous [X]).
    out2 is the leaf vector when ``leaf`` (chooseleaf), else == out.
    """
    T = tables
    X = x.shape[0]
    out = jnp.full((X, width), _NONE, dtype=jnp.int32)
    out2 = jnp.full((X, width), _NONE, dtype=jnp.int32)
    outpos = jnp.zeros((X,), dtype=jnp.int32)
    amb = jnp.zeros((X,), dtype=bool)
    roots = jnp.full((X,), root_row, dtype=jnp.int32)

    for rep in range(numrep):
        active0 = outpos < width

        def cond(st):
            active, ftotal, out, out2, outpos, amb = st
            return (active & (ftotal < tries)).any()

        def body(st):
            active, ftotal, out, out2, outpos, amb = st
            live = active & (ftotal < tries)
            r = jnp.int32(rep) + ftotal
            item, item_row, resolved, dead, empty, amb_d = _descend(
                T, x, roots, r, want_type, max_depth, ebmax
            )
            amb = amb | (live & amb_d)
            coll = _collides(out, outpos, item)
            if leaf:
                sub_r = (r >> (vary_r - 1)) if vary_r else jnp.zeros_like(r)
                rep2 = (
                    jnp.zeros_like(outpos) if stable else outpos
                )
                want_leaf = live & resolved & ~coll
                leaf_item, leaf_ok, amb2 = _leaf_firstn(
                    T, x, item_row, rep2, sub_r, out2, outpos, reweight,
                    recurse_tries, max_depth, ebmax, want_leaf,
                )
                amb = amb | (want_leaf & amb2)
                rej_leaf = want_leaf & ~leaf_ok
            else:
                leaf_item = item
                rej_leaf = jnp.zeros_like(live)
            if want_type == 0 and not leaf:
                rej_out = resolved & ~coll & _is_out_vec(x, reweight, item)
            else:
                rej_out = jnp.zeros_like(live)
            reject = empty | rej_leaf | rej_out
            ok = live & resolved & ~coll & ~reject
            # one-hot masked write instead of a row scatter (TPU scatters
            # with per-lane indices serialize; this was the engine's
            # dominant cost at 10^6 lanes)
            slotmask = jnp.arange(width)[None, :] == jnp.minimum(
                outpos, width - 1
            )[:, None]
            wmask = slotmask & ok[:, None]
            out = jnp.where(wmask, item[:, None], out)
            out2 = jnp.where(
                wmask, (leaf_item if leaf else item)[:, None], out2
            )
            outpos = outpos + ok.astype(jnp.int32)
            active = active & ~ok & ~(live & dead)  # dead = skip_rep
            fail = live & ~ok & ~dead
            ftotal = ftotal + fail.astype(jnp.int32)
            return active, ftotal, out, out2, outpos, amb

        st = (active0, jnp.zeros((X,), jnp.int32), out, out2, outpos, amb)
        _active, _ft, out, out2, outpos, amb = jax.lax.while_loop(
            cond, body, st
        )
    return out, out2, outpos, amb


# -- indep -------------------------------------------------------------------


def _leaf_indep(
    T, x, sub_rows, rep, parent_r, reweight,
    numrep: int, recurse_tries: int, max_depth: int, ebmax, want,
):
    """Leaf recursion of crush_choose_indep (mapper.c:426-449 via the
    python port): left=1 at slot ``rep``, type 0, its own retry rounds.
    The inner call's collision scope is only its own slot — which it
    resets to UNDEF on entry — so there is NO cross-slot leaf collision
    check (distinctness comes from the outer subtree collision), and a
    failed inner attempt is retried fresh by the next outer round.
    Returns (leaf, ok, ambiguous)."""
    X = x.shape[0]
    leaf = jnp.full((X,), _NONE, dtype=jnp.int32)
    done = jnp.zeros((X,), dtype=bool)
    deadf = jnp.zeros((X,), dtype=bool)
    amb = jnp.zeros((X,), dtype=bool)

    for ft2 in range(recurse_tries):
        live = want & ~done & ~deadf
        r2 = rep + parent_r + numrep * ft2
        item, _row, resolved, dead, empty, amb_d = _descend(
            T, x, sub_rows, r2, 0, max_depth, ebmax
        )
        amb = amb | (live & amb_d)
        rej = resolved & _is_out_vec(x, reweight, item)
        ok_now = live & resolved & ~rej
        leaf = jnp.where(ok_now, item, leaf)
        done = done | ok_now
        # wrong-type terminal: the inner call gives up (slot NONE) for
        # THIS attempt; the outer round retries with a fresh inner call
        deadf = deadf | (live & dead)
    return leaf, done, amb


@functools.partial(
    jax.jit,
    static_argnames=(
        "numrep", "out_size", "tries", "recurse_tries", "want_type",
        "leaf", "max_depth",
    ),
)
def choose_indep_hier(
    tables, x, root_row, reweight, ebmax,
    numrep: int, out_size: int, tries: int, recurse_tries: int,
    want_type: int, leaf: bool, max_depth: int,
):
    """Batched crush_choose_indep over a hierarchy (mapper.c:612).

    Returns (out [X,out_size], out2, ambiguous). Holes are NONE."""
    T = tables
    X = x.shape[0]
    out = jnp.full((X, out_size), _UNDEF, dtype=jnp.int32)
    out2 = jnp.full((X, out_size), _UNDEF, dtype=jnp.int32)
    amb = jnp.zeros((X,), dtype=bool)
    # root_row: a scalar (all lanes from one TAKE bucket) or an [X]
    # array (chained CHOOSE: each lane descends from ITS previous-step
    # bucket)
    roots = jnp.broadcast_to(
        jnp.asarray(root_row, dtype=jnp.int32), (X,)
    )

    def cond(st):
        ftotal, out, out2, amb = st
        return jnp.logical_and(
            ftotal < tries, (out == _UNDEF).any()
        )

    def body(st):
        ftotal, out, out2, amb = st
        for rep in range(out_size):
            need = out[:, rep] == _UNDEF
            r = jnp.int32(rep) + jnp.int32(numrep) * ftotal
            rv = jnp.broadcast_to(r, (X,)).astype(jnp.int32)
            item, item_row, resolved, dead, empty, amb_d = _descend(
                T, x, roots, rv, want_type, max_depth, ebmax
            )
            amb = amb | (need & amb_d)
            # permanent NONE: wrong-type terminal (depth dead-ends)
            perm = need & dead
            # collide against every slot of this call's region
            coll = (out == item[:, None]).any(axis=1)
            if leaf:
                want_leaf = need & resolved & ~coll
                leaf_item, leaf_ok, amb2 = _leaf_indep(
                    T, x, item_row, jnp.int32(rep), rv, reweight,
                    numrep, recurse_tries, max_depth, ebmax, want_leaf,
                )
                amb = amb | (want_leaf & amb2)
                rej_leaf = want_leaf & ~leaf_ok
            else:
                leaf_item = item
                rej_leaf = jnp.zeros_like(need)
            if want_type == 0 and not leaf:
                rej_out = resolved & ~coll & _is_out_vec(x, reweight, item)
            else:
                rej_out = jnp.zeros_like(need)
            ok = need & resolved & ~coll & ~rej_leaf & ~rej_out & ~perm
            out = out.at[:, rep].set(
                jnp.where(ok, item, jnp.where(perm, _NONE, out[:, rep]))
            )
            out2 = out2.at[:, rep].set(
                jnp.where(
                    ok, leaf_item if leaf else item,
                    jnp.where(perm, _NONE, out2[:, rep]),
                )
            )
        return ftotal + 1, out, out2, amb

    _ft, out, out2, amb = jax.lax.while_loop(
        cond, body, (jnp.int32(0), out, out2, amb)
    )
    out = jnp.where(out == _UNDEF, _NONE, out)
    out2 = jnp.where(out2 == _UNDEF, _NONE, out2)
    return out, out2, amb


# -- host-exact fallback engine (numpy, table-exact draws) -------------------
#
# Flagged lanes (runner-up inside the f32 error budget) are re-run here:
# host numpy has real vector gathers, so the exact 65536-entry draw
# tables apply directly over just the flagged subset. One scalar
# crush_do_rule call costs ~0.5 ms; at a ~0.7% flag rate over 10^6 x
# that was ~3.5 s — this batched exact engine makes it milliseconds.


class _NpTables:
    """Exact per-map tables for the host fallback (cached on MapTables)."""

    def __init__(self, cmap: CrushMap, T: MapTables):
        from .mapper_jax import _np_draw_table

        bids = sorted(cmap.buckets)
        B, I = T.B, T.I
        self.items = np.full((B, I), _NONE, dtype=np.int64)
        self.childrow = np.full((B, I), -1, dtype=np.int64)
        self.childtype = np.zeros((B, I), dtype=np.int64)
        self.size = np.zeros(B, dtype=np.int64)
        # exact draw tables deduped per distinct weight ([W, 65536] would
        # be [B, I, 65536] otherwise — gigabytes on a big map)
        wslot: dict[int, int] = {}
        tabs: list[np.ndarray] = []
        self.draw_slot = np.zeros((B, I), dtype=np.int64)
        for bi, bid in enumerate(bids):
            b = cmap.buckets[bid]
            self.size[bi] = len(b.items)
            for ii, (it, w) in enumerate(zip(b.items, b.item_weights)):
                self.items[bi, ii] = it
                w = int(w) if w > 0 else 0
                if w not in wslot:
                    wslot[w] = len(tabs)
                    tabs.append(_np_draw_table(w))
                self.draw_slot[bi, ii] = wslot[w]
                if it < 0 and it in cmap.buckets:
                    self.childrow[bi, ii] = T.row_of[it]
                    self.childtype[bi, ii] = cmap.buckets[it].type
        if 0 not in wslot:  # padding slots draw S64_MIN
            wslot[0] = len(tabs)
            tabs.append(_np_draw_table(0))
        self.pad_slot = wslot[0]
        self.draw_slot[self.items == _NONE] = self.pad_slot
        self.draw_tabs = np.stack(tabs)  # [W, 65536] int64


def _np_tables(cmap: CrushMap) -> _NpTables:
    T = tables_for(cmap)
    nt = getattr(T, "_np_tables", None)
    if nt is None:
        nt = _NpTables(cmap, T)
        T._np_tables = nt
    return nt


def _np_hash3(a, b, c):
    from .hashes import crush_hash32_3

    return crush_hash32_3(
        np.asarray(a, np.uint32), np.asarray(b, np.uint32),
        np.asarray(c, np.uint32),
    )


def _np_straw2_rows(NT, x, rows, r):
    """Exact straw2 per lane-varying bucket: (item, crow, ctype, empty).
    Column ``i`` is drawn only for the lanes whose bucket has more than
    ``i`` items (a 16-item host row does not pay for a 64-wide root)."""
    X = len(x)
    r = np.broadcast_to(r, (X,))
    best = np.zeros(X, dtype=np.int64)
    bit = np.full(X, _NONE, dtype=np.int64)
    brow = np.full(X, -1, dtype=np.int64)
    btyp = np.zeros(X, dtype=np.int64)
    szs = NT.size[rows]
    for i in range(NT.items.shape[1]):
        idx = np.nonzero(szs > i)[0]
        if not idx.size:
            break  # sizes only shrink past here
        ri = rows[idx]
        it = NT.items[ri, i]
        u = (_np_hash3(x[idx], it & 0xFFFFFFFF, r[idx])
             & np.uint32(0xFFFF)).astype(np.int64)
        d = NT.draw_tabs[NT.draw_slot[ri, i], u]
        if i:  # the first item wins outright, later ones on a larger draw
            keep = d > best[idx]
            idx, ri, it, d = idx[keep], ri[keep], it[keep], d[keep]
        best[idx] = d
        bit[idx] = it
        brow[idx] = NT.childrow[ri, i]
        btyp[idx] = NT.childtype[ri, i]
    return bit, brow, btyp, szs == 0


def _np_descend(NT, x, rows0, r, want_type, max_depth):
    X = len(x)
    r = np.broadcast_to(r, (X,))
    cur = rows0.copy()
    item = np.full(X, _NONE, dtype=np.int64)
    item_row = np.full(X, -1, dtype=np.int64)
    resolved = np.zeros(X, dtype=bool)
    dead = np.zeros(X, dtype=bool)
    empty_hit = np.zeros(X, dtype=bool)
    for _d in range(max_depth + 1):
        li = np.nonzero(~resolved & ~dead & ~empty_hit)[0]
        if not li.size:
            break
        it, crow, t, empty = _np_straw2_rows(
            NT, x[li], np.maximum(cur[li], 0), r[li])
        empty_hit[li[empty]] = True
        live = ~empty
        hit = live & (t == want_type)
        item[li[hit]] = it[hit]
        item_row[li[hit]] = crow[hit]
        resolved[li[hit]] = True
        godeep = live & ~hit & (it < 0) & (crow >= 0)
        dead[li[live & ~hit & ~godeep]] = True
        cur[li[godeep]] = crow[godeep]
    dead |= ~resolved & ~dead & ~empty_hit
    return item, item_row, resolved, dead, empty_hit


def _np_is_out(x, weight, item):
    from .hashes import crush_hash32_2

    n = len(weight)
    idx = np.clip(item, 0, n - 1)
    w = np.where((item < 0) | (item >= n), 0, np.asarray(weight)[idx])
    hashed = (
        crush_hash32_2(np.asarray(x, np.uint32),
                       np.asarray(item & 0xFFFFFFFF, np.uint32))
        & np.uint32(0xFFFF)
    ).astype(np.int64)
    return np.where(w >= 0x10000, False, np.where(w == 0, True, hashed >= w))


def _np_collides(out, outpos, item):
    W = out.shape[1]
    cols = np.arange(W)[None, :]
    return ((out == item[:, None]) & (cols < outpos[:, None])).any(axis=1)


def np_choose_firstn_hier(
    NT, x, root_row, weight,
    numrep, width, tries, recurse_tries, want_type, leaf, vary_r, stable,
    max_depth,
):
    """Host-exact mirror of choose_firstn_hier (same control flow,
    table-exact draws); each try runs only the lanes still choosing."""
    X = len(x)
    out = np.full((X, width), _NONE, dtype=np.int64)
    out2 = np.full((X, width), _NONE, dtype=np.int64)
    outpos = np.zeros(X, dtype=np.int64)
    roots = np.broadcast_to(np.asarray(root_row, dtype=np.int64), (X,))
    for rep in range(numrep):
        active = outpos < width
        ftotal = np.zeros(X, dtype=np.int64)
        while True:
            li = np.nonzero(active & (ftotal < tries))[0]
            if not li.size:
                break
            xl, pos = x[li], outpos[li]
            r = rep + ftotal[li]
            item, item_row, resolved, dead, empty = _np_descend(
                NT, xl, roots[li], r, want_type, max_depth
            )
            coll = _np_collides(out[li], pos, item)
            if leaf:
                sub_r = (r >> (vary_r - 1)) if vary_r else np.zeros_like(r)
                rep2 = np.zeros_like(pos) if stable else pos
                want_leaf = resolved & ~coll
                leaf_item, leaf_ok = _np_leaf_firstn(
                    NT, xl, item_row, rep2, sub_r, out2[li], pos, weight,
                    recurse_tries, max_depth, want_leaf,
                )
                rej_leaf = want_leaf & ~leaf_ok
            else:
                leaf_item = item
                rej_leaf = np.zeros(len(li), dtype=bool)
            if want_type == 0 and not leaf:
                rej_out = resolved & ~coll & _np_is_out(xl, weight, item)
            else:
                rej_out = np.zeros(len(li), dtype=bool)
            ok = resolved & ~coll & ~(empty | rej_leaf | rej_out)
            slot = np.minimum(pos, width - 1)
            out[li[ok], slot[ok]] = item[ok]
            out2[li[ok], slot[ok]] = leaf_item[ok]
            outpos[li] += ok
            active[li] &= ~ok & ~dead
            ftotal[li] += ~ok & ~dead
    return out, out2


def _np_leaf_firstn(
    NT, x, sub_rows, rep2, sub_r, out2, outpos, weight,
    recurse_tries, max_depth, want,
):
    X = len(x)
    leaf = np.full(X, _NONE, dtype=np.int64)
    done = np.zeros(X, dtype=bool)
    failed = np.zeros(X, dtype=bool)
    ftotal = np.zeros(X, dtype=np.int64)
    for _t in range(recurse_tries):
        li = np.nonzero(want & ~done & ~failed
                        & (ftotal < recurse_tries))[0]
        if not li.size:
            break
        item, _row, resolved, dead, empty = _np_descend(
            NT, x[li], np.maximum(sub_rows[li], 0),
            rep2[li] + sub_r[li] + ftotal[li], 0, max_depth
        )
        coll = _np_collides(out2[li], outpos[li], item)
        ok_now = resolved & ~(coll | _np_is_out(x[li], weight, item))
        leaf[li[ok_now]] = item[ok_now]
        done[li[ok_now]] = True
        failed[li[dead]] = True
        ftotal[li] += ~ok_now & ~dead
    return leaf, done


def np_choose_indep_hier(
    NT, x, root_row, weight,
    numrep, out_size, tries, recurse_tries, want_type, leaf, max_depth,
):
    """Host-exact mirror of choose_indep_hier; each (try, slot) runs
    only the lanes whose slot is still undecided."""
    X = len(x)
    out = np.full((X, out_size), _UNDEF, dtype=np.int64)
    out2 = np.full((X, out_size), _UNDEF, dtype=np.int64)
    # scalar root (one TAKE bucket) or per-lane roots (chained steps)
    roots = np.broadcast_to(np.asarray(root_row, dtype=np.int64), (X,))
    for ftotal in range(tries):
        if not (out == _UNDEF).any():
            break
        for rep in range(out_size):
            li = np.nonzero(out[:, rep] == _UNDEF)[0]
            if not li.size:
                continue
            xl = x[li]
            r = np.full(len(li), rep + numrep * ftotal, dtype=np.int64)
            item, item_row, resolved, dead, empty = _np_descend(
                NT, xl, roots[li], r, want_type, max_depth
            )
            coll = (out[li] == item[:, None]).any(axis=1)
            if leaf:
                want_leaf = resolved & ~coll
                leaf_item, leaf_ok = _np_leaf_indep(
                    NT, xl, item_row, rep, r, weight,
                    numrep, recurse_tries, max_depth, want_leaf,
                )
                rej_leaf = want_leaf & ~leaf_ok
            else:
                leaf_item = item
                rej_leaf = np.zeros(len(li), dtype=bool)
            if want_type == 0 and not leaf:
                rej_out = resolved & ~coll & _np_is_out(xl, weight, item)
            else:
                rej_out = np.zeros(len(li), dtype=bool)
            ok = resolved & ~coll & ~rej_leaf & ~rej_out & ~dead
            out[li[ok], rep] = item[ok]
            out2[li[ok], rep] = leaf_item[ok]
            out[li[dead], rep] = _NONE
            out2[li[dead], rep] = _NONE
    out = np.where(out == _UNDEF, _NONE, out)
    out2 = np.where(out2 == _UNDEF, _NONE, out2)
    return out, out2


def _np_leaf_indep(
    NT, x, sub_rows, rep, parent_r, weight,
    numrep, recurse_tries, max_depth, want,
):
    X = len(x)
    parent_r = np.broadcast_to(parent_r, (X,))
    leaf = np.full(X, _NONE, dtype=np.int64)
    done = np.zeros(X, dtype=bool)
    deadf = np.zeros(X, dtype=bool)
    for ft2 in range(recurse_tries):
        li = np.nonzero(want & ~done & ~deadf)[0]
        if not li.size:
            break
        item, _row, resolved, dead, empty = _np_descend(
            NT, x[li], np.maximum(sub_rows[li], 0),
            rep + parent_r[li] + numrep * ft2, 0, max_depth
        )
        ok_now = resolved & ~_np_is_out(x[li], weight, item)
        leaf[li[ok_now]] = item[ok_now]
        done[li[ok_now]] = True
        deadf[li[dead]] = True
    return leaf, done


def np_do_rule_hier(cmap, ruleno, xs, result_max, weight=None) -> np.ndarray:
    """Host-exact batched crush_do_rule for supported hierarchical rules
    (the fallback engine; also an independent oracle for tests)."""
    take, chooses, tries, leaf_tries, vary_r, stable = _rule_shape(
        cmap, ruleno
    )
    if len(chooses) > 1:
        return _np_chain(
            cmap, ruleno, take, chooses, tries, leaf_tries, xs,
            result_max, weight,
        )
    choose = chooses[0]
    t = cmap.tunables
    firstn = choose.op in (
        CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSELEAF_FIRSTN
    )
    leaf = choose.op in (
        CRUSH_RULE_CHOOSELEAF_FIRSTN, CRUSH_RULE_CHOOSELEAF_INDEP
    )
    numrep = choose.arg1 if choose.arg1 > 0 else choose.arg1 + result_max
    if numrep <= 0:
        return np.zeros((len(xs), 0), dtype=np.int32)
    want_type = choose.arg2
    if weight is None:
        weight = cmap.get_weights()
    T = tables_for(cmap)
    NT = _np_tables(cmap)
    xs = np.asarray(xs, dtype=np.uint32)
    root_row = T.row_of[take]
    if firstn:
        if leaf_tries:
            recurse_tries = leaf_tries
        elif t.chooseleaf_descend_once:
            recurse_tries = 1
        else:
            recurse_tries = tries
        width = min(numrep, result_max)
        out, out2 = np_choose_firstn_hier(
            NT, xs, root_row, weight, numrep, width, tries,
            recurse_tries, want_type, leaf, vary_r, stable, T.depth,
        )
    else:
        out_size = min(numrep, result_max)
        recurse_tries = leaf_tries if leaf_tries else 1
        out, out2 = np_choose_indep_hier(
            NT, xs, root_row, weight, numrep, out_size, tries,
            recurse_tries, want_type, leaf, T.depth,
        )
    return (out2 if leaf else out).astype(np.int32)


def _np_chain(cmap, ruleno, take, chooses, tries, leaf_tries, xs,
              result_max, weight) -> np.ndarray:
    """Host-EXACT chained INDEP steps, batched (mirrors _chain_engine
    with the exact numpy engine — no draw ambiguity on the host, real
    table gathers).  Only lanes whose scalar semantics diverge from the
    slotted model (a previous-step slot that is NONE/a device, which the
    scalar interpreter COMPACTS over; or a mid-chain result_max clamp)
    re-run the full scalar interpreter, and those are rare exhaustion
    cases — not the ~10% of lanes the f32 device draw flags."""
    indep_ops = (CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_CHOOSELEAF_INDEP)
    if any(c.op not in indep_ops for c in chooses):
        # supports_hier gates the production path; direct oracle use of
        # a firstn chain must fail LOUDLY, not return indep semantics
        raise ValueError(
            "multi-step chains are only implemented for INDEP steps"
        )
    if weight is None:
        weight = cmap.get_weights()
    T = tables_for(cmap)
    NT = _np_tables(cmap)
    xs = np.asarray(xs, dtype=np.uint32)
    X = len(xs)
    total = 1
    for c in chooses:
        total *= max(c.arg1, 1)
    final_w = min(total, result_max)

    def scalar_rows(idxs: np.ndarray, out: np.ndarray) -> None:
        from .mapper import Workspace, crush_do_rule

        ws = Workspace(cmap)
        for i in idxs:
            res = crush_do_rule(
                cmap, ruleno, int(xs[i]), result_max, weight=weight,
                workspace=ws,
            )
            out[i, :] = _NONE
            out[i, : min(len(res), final_w)] = res[:final_w]

    first = chooses[0]
    n1 = first.arg1
    cur, _o2 = np_choose_indep_hier(
        NT, xs, T.row_of[take], weight, n1, n1, tries, 1,
        first.arg2, False, T.depth,
    )
    width = n1
    odd = np.zeros(X, dtype=bool)  # lanes needing scalar semantics
    clamped = False
    for step in chooses[1:]:
        leaf_s = step.op == CRUSH_RULE_CHOOSELEAF_INDEP
        n_s = step.arg1
        if width * n_s > result_max:
            clamped = True
            break
        recurse_tries = leaf_tries if leaf_tries else 1
        is_bucket = cur < 0
        idx = np.clip(-1 - cur, 0, T.id2row.shape[0] - 1)
        rows = np.where(is_bucket, T.id2row[idx], -1)
        valid = rows >= 0
        odd |= (~valid).any(axis=1)
        x_flat = np.repeat(xs, width)
        rows_flat = np.where(valid, rows, 0).reshape(-1)
        o, o2 = np_choose_indep_hier(
            NT, x_flat, rows_flat, weight, n_s, n_s, tries,
            recurse_tries, step.arg2, leaf_s, T.depth,
        )
        use = (o2 if leaf_s else o).reshape(X, width, n_s)
        use = np.where(valid[:, :, None], use, _NONE)
        cur = use.reshape(X, width * n_s)
        width *= n_s
    if clamped:
        out = np.full((X, final_w), _NONE, dtype=np.int32)
        scalar_rows(np.arange(X), out)
        return out
    out = cur.astype(np.int32)
    if odd.any():
        scalar_rows(np.nonzero(odd)[0], out)
    return out


# -- rule-level driver -------------------------------------------------------


def _rule_shape(cmap: CrushMap, ruleno: int):
    """(take_bucket_id, [choose_steps...], tries, leaf_tries, vary_r,
    stable) or None if the rule is not one TAKE -> CHOOSE+ -> EMIT
    chain.  Multi-step chains (the LRC per-layer rules: TAKE ->
    CHOOSE_INDEP locality -> CHOOSELEAF_INDEP domain -> EMIT,
    reference:src/erasure-code/lrc/ErasureCodeLrc.cc:44 ruleset_steps)
    return more than one choose step."""
    if ruleno < 0 or ruleno >= len(cmap.rules) or cmap.rules[ruleno] is None:
        return None
    t = cmap.tunables
    tries = t.choose_total_tries + 1
    leaf_tries = 0
    vary_r = t.chooseleaf_vary_r
    stable = t.chooseleaf_stable
    take = None
    chooses: list = []
    stage = 0
    for s in cmap.rules[ruleno].steps:
        if s.op == CRUSH_RULE_SET_CHOOSE_TRIES:
            if s.arg1 > 0:
                tries = s.arg1
            continue
        if s.op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
            if s.arg1 > 0:
                leaf_tries = s.arg1
            continue
        if s.op == CRUSH_RULE_SET_CHOOSELEAF_VARY_R:
            if s.arg1 >= 0:
                vary_r = s.arg1
            continue
        if s.op == CRUSH_RULE_SET_CHOOSELEAF_STABLE:
            if s.arg1 >= 0:
                stable = s.arg1
            continue
        if s.op in (
            CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
            CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
        ):
            if s.arg1 > 0:
                return None
            continue
        if stage == 0 and s.op == CRUSH_RULE_TAKE:
            take = s.arg1
            stage = 1
        elif stage == 1 and s.op in _CHOOSE_OPS:
            chooses.append(s)
        elif stage == 1 and s.op == CRUSH_RULE_EMIT and chooses:
            stage = 3
        else:
            return None
    if stage != 3 or take is None or not chooses:
        return None
    return take, chooses, tries, leaf_tries, vary_r, stable


def supports_hier(cmap: CrushMap, ruleno: int) -> bool:
    """True if vec_do_rule_hier handles this (map, rule) bit-exactly."""
    t = cmap.tunables
    if t.choose_local_tries != 0 or t.choose_local_fallback_tries != 0:
        return False
    shape = _rule_shape(cmap, ruleno)
    if shape is None:
        return False
    take, chooses, _tries, _lt, vary_r, _stable = shape
    if take not in cmap.buckets:
        return False
    if vary_r < 0 or vary_r > 3:
        return False
    if len(chooses) > 1:
        # chained steps (LRC per-layer rules): supported when every step
        # is INDEP (firstn chains compact their output — different osize
        # algebra), intermediates select BUCKET types with a positive
        # count, and the slot product fits result-independent widths
        indep_ops = (CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_CHOOSELEAF_INDEP)
        if any(c.op not in indep_ops for c in chooses):
            return False
        if any(c.arg1 <= 0 for c in chooses):
            return False
        for c in chooses[:-1]:
            if c.op != CRUSH_RULE_CHOOSE_INDEP or c.arg2 == 0:
                return False
    choose = chooses[-1]
    leaf = choose.op in (
        CRUSH_RULE_CHOOSELEAF_FIRSTN, CRUSH_RULE_CHOOSELEAF_INDEP
    )
    if leaf and choose.arg2 == 0:
        return False  # chooseleaf to type 0 is not a real shape
    # every bucket straw2, acyclic, devices in range
    seen: set[int] = set()

    def walk(bid: int) -> bool:
        if bid in seen:
            return False  # cycle
        seen.add(bid)
        b = cmap.buckets.get(bid)
        if b is None or b.alg != CRUSH_BUCKET_STRAW2:
            return False
        for it in b.items:
            if it >= 0:
                if it >= cmap.max_devices:
                    return False
            elif it in cmap.buckets:
                if not walk(it):
                    return False
            else:
                return False
        seen.discard(bid)  # path-scoped for DAG-shared subtrees
        return True

    return walk(take)


def _hier_engine(cmap, ruleno, xs_np, result_max, weight):
    """Run the hierarchical engine; (out_dev [X,W], amb_dev [X]) or None
    (degenerate numrep).  Device arrays: callers choose what to fetch
    (vec_do_rule_hier fetches rows; vec_rule_stats bincounts on device)."""
    take, chooses, tries, leaf_tries, vary_r, stable = _rule_shape(
        cmap, ruleno
    )
    t = cmap.tunables
    if weight is None:
        weight = cmap.get_weights()
    T = tables_for(cmap)
    x = jnp.asarray(xs_np)
    rw = jnp.asarray(np.array(weight, dtype=np.int32))
    ebm = jnp.float32(T.ebmax)
    root_row = T.row_of[take]

    if len(chooses) > 1:
        return _chain_engine(
            cmap, T, x, rw, ebm, root_row, chooses, tries, leaf_tries,
            result_max,
        )

    choose = chooses[0]
    firstn = choose.op in (
        CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSELEAF_FIRSTN
    )
    leaf = choose.op in (
        CRUSH_RULE_CHOOSELEAF_FIRSTN, CRUSH_RULE_CHOOSELEAF_INDEP
    )
    numrep = choose.arg1 if choose.arg1 > 0 else choose.arg1 + result_max
    if numrep <= 0:
        return None
    want_type = choose.arg2

    if firstn:
        if leaf_tries:
            recurse_tries = leaf_tries
        elif t.chooseleaf_descend_once:
            recurse_tries = 1
        else:
            recurse_tries = tries
        width = min(numrep, result_max)
        out, out2, _outpos, amb = choose_firstn_hier(
            T.tree(), x, root_row, rw, ebm,
            numrep=int(numrep), width=int(width), tries=int(tries),
            recurse_tries=int(recurse_tries), want_type=int(want_type),
            leaf=bool(leaf), vary_r=int(vary_r), stable=int(stable),
            max_depth=int(T.depth),
        )
        # firstn result is compact (no holes): the engine writes
        # sequentially per lane, so rows are already left-packed
    else:
        out_size = min(numrep, result_max)
        recurse_tries = leaf_tries if leaf_tries else 1
        out, out2, amb = choose_indep_hier(
            T.tree(), x, root_row, rw, ebm,
            numrep=int(numrep), out_size=int(out_size), tries=int(tries),
            recurse_tries=int(recurse_tries), want_type=int(want_type),
            leaf=bool(leaf), max_depth=int(T.depth),
        )
    return (out2 if leaf else out), amb


def _chain_engine(cmap, T, x, rw, ebm, root_row, chooses, tries,
                  leaf_tries, result_max):
    """Chained INDEP steps on device (the LRC per-layer rules).

    Scalar semantics (mapper.c do_rule CHOOSE loop + our pinned
    crush/mapper.py): each later step runs crush_choose_indep once PER
    BUCKET of the previous step's output, with outpos=0 and parent_r=0 —
    i.e. an independent engine run rooted at that bucket — and the
    per-bucket regions concatenate.  A previous-step slot that is NONE
    or a device makes the scalar path COMPACT its output (the bucket is
    skipped and osize does not advance); such lanes are flagged
    ambiguous and recomputed exactly on the host."""
    X = x.shape[0]
    id2row = jnp.asarray(T.id2row)
    nrow = T.id2row.shape[0]

    # step 1 from the TAKE root (plain INDEP choose of buckets)
    first = chooses[0]
    n1 = first.arg1
    cur, _o2, amb = choose_indep_hier(
        T.tree(), x, root_row, rw, ebm,
        numrep=int(n1), out_size=int(n1), tries=int(tries),
        recurse_tries=1, want_type=int(first.arg2), leaf=False,
        max_depth=int(T.depth),
    )
    width = n1
    for step in chooses[1:]:
        leaf_s = step.op == CRUSH_RULE_CHOOSELEAF_INDEP
        n_s = step.arg1
        if width * n_s > result_max:
            # scalar would clamp per-slot out_size mid-chain; rare and
            # shape-dependent — recompute everything exactly on the
            # host.  Pad to the host fallback's width so the splice in
            # vec_do_rule_hier shape-matches (values are irrelevant:
            # every lane is flagged).
            amb = amb | jnp.ones((X,), dtype=bool)
            total = 1
            for c in chooses:
                total *= max(c.arg1, 1)
            pad_w = min(total, result_max)
            if pad_w > cur.shape[1]:
                cur = jnp.concatenate(
                    [cur, jnp.full((X, pad_w - cur.shape[1]), _NONE,
                                   dtype=jnp.int32)], axis=1,
                )
            else:
                cur = cur[:, :pad_w]
            break
        recurse_tries = leaf_tries if leaf_tries else 1
        # ONE flattened dispatch per step (not one per column): lanes
        # become [X*width] with x repeated per slot and each flat lane
        # rooted at its slot's bucket; the [X*width, n_s] output
        # reshapes to the slot-major concatenation the scalar produces
        is_bucket = cur < 0  # NONE is positive, devices are >= 0
        idx = jnp.clip(-1 - cur, 0, nrow - 1)
        rows = jnp.where(is_bucket, id2row[idx], -1)  # [X, width]
        valid = rows >= 0
        amb = amb | (~valid).any(axis=1)
        x_flat = jnp.repeat(x, width)
        rows_flat = jnp.where(valid, rows, 0).reshape(-1)
        o_s, o2_s, amb_s = choose_indep_hier(
            T.tree(), x_flat, rows_flat, rw, ebm,
            numrep=int(n_s), out_size=int(n_s), tries=int(tries),
            recurse_tries=int(recurse_tries),
            want_type=int(step.arg2), leaf=leaf_s,
            max_depth=int(T.depth),
        )
        use = (o2_s if leaf_s else o_s).reshape(X, width, n_s)
        use = jnp.where(valid[:, :, None], use, _NONE)
        cur = use.reshape(X, width * n_s)
        amb = amb | amb_s.reshape(X, width).any(axis=1)
        width *= n_s
    return cur, amb


def vec_do_rule_hier(
    cmap: CrushMap,
    ruleno: int,
    xs,
    result_max: int,
    weight=None,
) -> np.ndarray:
    """Batched crush_do_rule over a hierarchical map; bit-identical to the
    scalar mapper for supported (map, rule) shapes."""
    if not supports_hier(cmap, ruleno):
        raise ValueError("map/rule shape not supported by the hier vec path")
    xs_np = np.asarray(xs, dtype=np.uint32)
    eng = _hier_engine(cmap, ruleno, xs_np, result_max, weight)
    if eng is None:
        return np.zeros((len(xs_np), 0), dtype=np.int32)
    out_dev, amb_dev = eng
    res = np.array(out_dev)
    amb = np.asarray(amb_dev)
    if amb.any():
        flagged = np.nonzero(amb)[0]
        res[flagged] = np_do_rule_hier(
            cmap, ruleno, xs_np[flagged], result_max, weight
        )
    return res
