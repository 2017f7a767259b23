"""Plain CRUSH reference for erasure-coded placement: straw2 buckets and
``choose_indep`` / ``chooseleaf_indep`` rules, one input at a time.

Follows upstream ``src/crush/mapper.c`` (``crush_do_rule``,
``crush_choose_indep``, ``bucket_straw2_choose``, ``crush_ln``) and
``hash.c`` (rjenkins1), on plain Python integers.  The log tables are
the protocol's own constants (``crush_ln_tables.py``, a copy).  The map
is plain data, built from the configuration by :func:`build_map`.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.crush_ln_tables import LL_TBL, RH_LH_TBL

M32 = 0xFFFFFFFF
SEED = 1315423911
ITEM_NONE = 0x7FFFFFFF
_UNDEF = 0x7FFFFFFE
_S64_MIN = -(1 << 63)


def _mix(a, b, c):
    a = (a - b - c) & M32; a ^= c >> 13
    b = (b - c - a) & M32; b ^= (a << 8) & M32
    c = (c - a - b) & M32; c ^= b >> 13
    a = (a - b - c) & M32; a ^= c >> 12
    b = (b - c - a) & M32; b ^= (a << 16) & M32
    c = (c - a - b) & M32; c ^= b >> 5
    a = (a - b - c) & M32; a ^= c >> 3
    b = (b - c - a) & M32; b ^= (a << 10) & M32
    c = (c - a - b) & M32; c ^= b >> 15
    return a, b, c


def hash32_2(a, b):
    h = (SEED ^ a ^ b) & M32
    x, y = 231232, 1232
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a, b, c):
    h = (SEED ^ a ^ b ^ c) & M32
    x, y = 231232, 1232
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def crush_ln(xin):
    """2^44 * log2(xin + 1), fixed point."""
    x = (xin + 1) & M32
    iexpon = 15
    if not (x & 0x18000):
        bits = 16 - (x & 0x1FFFF).bit_length()
        x = (x << bits) & M32
        iexpon = 15 - bits
    index1 = (x >> 8) << 1
    rh = RH_LH_TBL[index1 - 256]
    lh = RH_LH_TBL[index1 + 1 - 256]
    xl64 = (x * rh) >> 48
    lh += LL_TBL[xl64 & 0xFF]
    return (iexpon << 44) + (lh >> 4)


def straw2_choose(bucket, x, r):
    high, high_draw = 0, 0
    for i, (item, w) in enumerate(zip(bucket["items"], bucket["weights"])):
        if w:
            u = hash32_3(x & M32, item & M32, r & M32) & 0xFFFF
            ln = crush_ln(u) - 0x1000000000000
            draw = -((-ln) // w)  # C division truncates toward zero
        else:
            draw = _S64_MIN
        if i == 0 or draw > high_draw:
            high, high_draw = i, draw
    return bucket["items"][high]


def is_out(weight, item, x):
    if item >= len(weight):
        return True
    w = weight[item]
    if w >= 0x10000:
        return False
    if w == 0:
        return True
    return (hash32_2(x & M32, item & M32) & 0xFFFF) >= w


def choose_indep(cmap, bucket, weight, x, left, numrep, type_, out, outpos,
                 tries, recurse_tries, recurse_to_leaf, out2, parent_r):
    buckets, n_dev = cmap["buckets"], cmap["max_devices"]
    endpos = outpos + left
    for rep in range(outpos, endpos):
        out[rep] = _UNDEF
        if out2 is not None:
            out2[rep] = _UNDEF
    ftotal = 0
    while left > 0 and ftotal < tries:
        for rep in range(outpos, endpos):
            if out[rep] != _UNDEF:
                continue
            in_b = bucket
            while True:
                r = rep + parent_r + numrep * ftotal
                if not in_b["items"]:
                    break
                item = straw2_choose(in_b, x, r)
                if item >= n_dev:
                    out[rep] = ITEM_NONE
                    if out2 is not None:
                        out2[rep] = ITEM_NONE
                    left -= 1
                    break
                itemtype = buckets[item]["type"] if item < 0 else 0
                if itemtype != type_:
                    if item >= 0 or item not in buckets:
                        out[rep] = ITEM_NONE
                        if out2 is not None:
                            out2[rep] = ITEM_NONE
                        left -= 1
                        break
                    in_b = buckets[item]
                    continue
                if item in out[outpos:endpos]:
                    break
                if recurse_to_leaf:
                    if item < 0:
                        choose_indep(cmap, buckets[item], weight, x, 1,
                                     numrep, 0, out2, rep, recurse_tries, 0,
                                     False, None, r)
                        if out2[rep] == ITEM_NONE:
                            break
                    else:
                        out2[rep] = item
                if itemtype == 0 and is_out(weight, item, x):
                    break
                out[rep] = item
                left -= 1
                break
        ftotal += 1
    for rep in range(outpos, endpos):
        if out[rep] == _UNDEF:
            out[rep] = ITEM_NONE
        if out2 is not None and out2[rep] == _UNDEF:
            out2[rep] = ITEM_NONE


def do_rule(cmap, x, result_max, weight=None):
    """Steps: set_chooseleaf_tries, set_choose_tries, take,
    choose_indep / chooseleaf_indep, emit."""
    if weight is None:
        weight = [0x10000] * cmap["max_devices"]
    choose_tries = cmap["tunables"]["choose_total_tries"] + 1
    leaf_tries = 0
    result, w = [], []
    for op, arg1, arg2 in cmap["rule"]:
        if op == "set_choose_tries":
            if arg1 > 0:
                choose_tries = arg1
        elif op == "set_chooseleaf_tries":
            if arg1 > 0:
                leaf_tries = arg1
        elif op == "take":
            w = [arg1]
        elif op in ("choose_indep", "chooseleaf_indep"):
            leaf = op == "chooseleaf_indep"
            o, c, osize = [0] * result_max, [0] * result_max, 0
            for wi in w:
                numrep = arg1 if arg1 > 0 else arg1 + result_max
                if numrep <= 0 or wi not in cmap["buckets"]:
                    continue
                size = min(numrep, result_max - osize)
                o_sub, c_sub = o[osize:], c[osize:]
                choose_indep(cmap, cmap["buckets"][wi], weight, x, size,
                             numrep, arg2, o_sub, 0, choose_tries,
                             leaf_tries or 1, leaf, c_sub, 0)
                o[osize:], c[osize:] = o_sub, c_sub
                osize += size
            w = (c if leaf else o)[:osize]
        elif op == "emit":
            result += w[:result_max - len(result)]
            w = []
        else:
            raise ValueError(f"reference has no step {op!r}")
    return result


def build_map(crush: dict) -> dict:
    """A ``crushtool --build`` map: ``n_osds`` devices of weight 1.0 in
    hosts of ``osds_per_host`` (host buckets -1, -2, ... in order, type
    1), under one straw2 root (the next id, type 2), with the rule
    ``crush["rule"]``, whose ``take`` names the root as ``"root"``."""
    n, per = crush["n_osds"], crush["osds_per_host"]
    buckets, hosts = {}, []
    for h, first in enumerate(range(0, n, per)):
        items = list(range(first, min(first + per, n)))
        buckets[-1 - h] = {"type": 1, "items": items,
                           "weights": [0x10000] * len(items)}
        hosts.append(-1 - h)
    root = -1 - len(hosts)
    buckets[root] = {"type": 2, "items": hosts,
                     "weights": [sum(buckets[b]["weights"]) for b in hosts]}
    rule = [(op, root if a1 == "root" else a1, a2)
            for op, a1, a2 in crush["rule"]]
    return {"buckets": buckets, "max_devices": n, "root": root,
            "rule": rule, "tunables": crush["tunables"]}


# -- the same algorithm over many inputs at once ------------------------------
#
# do_rule_many follows do_rule step for step, with the inputs as numpy
# lanes: every lane runs the same rep and try loop, and a lane whose slot
# is already filled sits that iteration out.  tests/benchmark checks it
# against do_rule.


def _mix_v(a, b, c):
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


def _u32(v):
    return (np.asarray(v, dtype=np.int64) & M32).astype(np.uint32)


def hash32_3_v(a, b, c):
    a, b, c = np.broadcast_arrays(_u32(a), _u32(b), _u32(c))
    h = np.uint32(SEED) ^ a ^ b ^ c
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix_v(a, b, h)
    c, x, h = _mix_v(c, x, h)
    y, a, h = _mix_v(y, a, h)
    b, x, h = _mix_v(b, x, h)
    y, c, h = _mix_v(y, c, h)
    return h


def hash32_2_v(a, b):
    a, b = np.broadcast_arrays(_u32(a), _u32(b))
    h = np.uint32(SEED) ^ a ^ b
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix_v(a, b, h)
    x, a, h = _mix_v(x, a, h)
    b, y, h = _mix_v(b, y, h)
    return h


class Lanes:
    """The map as padded arrays, for many inputs at once."""

    def __init__(self, cmap):
        ids = sorted(cmap["buckets"], reverse=True)  # -1, -2, ...
        if ids != list(range(-1, -len(ids) - 1, -1)):
            raise ValueError("bucket ids must be -1 .. -n")
        width = max(len(b["items"]) for b in cmap["buckets"].values())
        self.items = np.zeros((len(ids), width), dtype=np.int64)
        self.weights = np.zeros((len(ids), width), dtype=np.int64)
        self.types = np.zeros(len(ids), dtype=np.int64)
        for i, bid in enumerate(ids):
            b = cmap["buckets"][bid]
            self.items[i, :len(b["items"])] = b["items"]
            self.weights[i, :len(b["weights"])] = b["weights"]
            self.types[i] = b["type"]
        self.n_dev = cmap["max_devices"]
        self.ln = np.array([crush_ln(u) - 0x1000000000000
                            for u in range(0x10000)], dtype=np.int64)

    def straw2(self, bidx, x, r):
        items, w = self.items[bidx], self.weights[bidx]
        u = hash32_3_v(x[:, None], items, r[:, None]) & np.uint32(0xFFFF)
        draw = np.where(w > 0, -((-self.ln[u]) // np.maximum(w, 1)),
                        _S64_MIN)
        return items[np.arange(len(bidx)), np.argmax(draw, axis=1)]

    def descend(self, bidx, x, r, type_):
        """(item, none): straw2 down from bucket ``bidx`` until an item of
        ``type_`` (a device for 0), as choose_indep's inner loop."""
        item = np.zeros(len(bidx), dtype=np.int64)
        none = np.zeros(len(bidx), dtype=bool)
        live = np.ones(len(bidx), dtype=bool)
        cur = bidx.copy()
        while live.any():
            got = self.straw2(cur[live], x[live], r[live])
            idx = np.nonzero(live)[0]
            item[idx] = got
            big = got >= self.n_dev
            itype = np.where(got < 0, self.types[np.maximum(-1 - got, 0)], 0)
            bad = big | ((itype != type_) & (got >= 0))
            none[idx[bad]] = True
            deeper = ~bad & (itype != type_)
            cur[idx[deeper]] = -1 - got[deeper]
            live[idx[~deeper]] = False
        return item, none

    def is_out(self, weight, item, x):
        w = weight[np.minimum(item, len(weight) - 1)]
        h = hash32_2_v(x, item) & np.uint32(0xFFFF)
        return (item >= len(weight)) | ((w < 0x10000) & ((w == 0) | (h >= w)))


def _choose_indep_v(lanes, bidx, weight, x, left, numrep, type_, tries,
                    recurse_tries, leaf):
    n = len(x)
    out = np.full((n, left), _UNDEF, dtype=np.int64)
    out2 = np.full((n, left), _UNDEF, dtype=np.int64)
    for ftotal in range(tries):
        if not (out == _UNDEF).any():
            break
        for rep in range(left):
            act = np.nonzero(out[:, rep] == _UNDEF)[0]
            if not len(act):
                continue
            r = np.full(len(act), rep + numrep * ftotal, dtype=np.int64)
            item, none = lanes.descend(bidx[act], x[act], r, type_)
            out[act[none], rep] = ITEM_NONE
            out2[act[none], rep] = ITEM_NONE
            keep = ~none & ~(out[act] == item[:, None]).any(axis=1)
            act, item, r = act[keep], item[keep], r[keep]
            if leaf:
                inner = np.where(item >= 0, item, _UNDEF)
                sub = np.nonzero(item < 0)[0]
                val = np.full(len(sub), _UNDEF, dtype=np.int64)
                for f2 in range(recurse_tries):
                    todo = np.nonzero(val == _UNDEF)[0]
                    if not len(todo):
                        break
                    s = sub[todo]
                    r2 = rep + r[s] + numrep * f2
                    it2, none2 = lanes.descend(-1 - item[s], x[act[s]], r2, 0)
                    val[todo[none2]] = ITEM_NONE
                    ok = ~none2 & ~lanes.is_out(weight, it2, x[act[s]])
                    val[todo[ok]] = it2[ok]
                val[val == _UNDEF] = ITEM_NONE
                inner[sub] = val
                out2[act, rep] = inner
                placed = inner != ITEM_NONE
                act, item = act[placed], item[placed]
            dev = item >= 0
            rejected = np.zeros(len(item), dtype=bool)
            if type_ == 0:
                rejected[dev] = lanes.is_out(weight, item[dev], x[act[dev]])
            out[act[~rejected], rep] = item[~rejected]
    out[out == _UNDEF] = ITEM_NONE
    out2[out2 == _UNDEF] = ITEM_NONE
    return out2 if leaf else out


def do_rule_many(cmap, lanes, xs, result_max, weight=None):
    """[len(xs), result_max] int64 of ``do_rule`` for every input, for
    rules of one take, one choose step and one emit."""
    x = np.asarray(xs, dtype=np.int64) & M32
    weight = np.asarray(weight if weight is not None
                        else [0x10000] * cmap["max_devices"], dtype=np.int64)
    choose_tries = cmap["tunables"]["choose_total_tries"] + 1
    leaf_tries = 0
    out = None
    for op, arg1, arg2 in cmap["rule"]:
        if op == "set_choose_tries":
            choose_tries = arg1 if arg1 > 0 else choose_tries
        elif op == "set_chooseleaf_tries":
            leaf_tries = arg1 if arg1 > 0 else leaf_tries
        elif op == "take":
            take = arg1
        elif op in ("choose_indep", "chooseleaf_indep"):
            numrep = arg1 if arg1 > 0 else arg1 + result_max
            out = _choose_indep_v(
                lanes, np.full(len(x), -1 - take, dtype=np.int64), weight,
                x, min(numrep, result_max), numrep, arg2, choose_tries,
                leaf_tries or 1, op == "chooseleaf_indep")
        elif op != "emit":
            raise ValueError(f"reference has no step {op!r}")
    return out
