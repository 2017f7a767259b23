"""The placement surface: ``crushtool --test --show-mappings`` for the
pool's EC rule on the configuration's map, through the program's
vectorized mapper (``mapper_jax.vec_do_rule``: the device rule program
plus the host's exact re-run of the lanes it flags).

The map is data: ``n_osds`` devices in hosts of ``osds_per_host`` under
one straw2 root, with the configuration's tunables and rule, built
through the program's ``CrushMap`` as ``crushtool --build`` would.  One
caller in a closed loop maps ``inputs_per_call`` consecutive inputs per
call from a seeded start.  A seeded sample of the inputs mapped in the
window is compared with the plain reference mapper, run over the whole
sample at once.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import crush as ref

_OPS = {"take": "CRUSH_RULE_TAKE", "emit": "CRUSH_RULE_EMIT",
        "choose_indep": "CRUSH_RULE_CHOOSE_INDEP",
        "chooseleaf_indep": "CRUSH_RULE_CHOOSELEAF_INDEP",
        "set_choose_tries": "CRUSH_RULE_SET_CHOOSE_TRIES",
        "set_chooseleaf_tries": "CRUSH_RULE_SET_CHOOSELEAF_TRIES"}
_TUNABLES = ("choose_local_tries", "choose_local_fallback_tries",
             "choose_total_tries", "chooseleaf_descend_once",
             "chooseleaf_vary_r", "chooseleaf_stable", "straw_calc_version")


def program_map(crush: dict, plain: dict):
    """(CrushMap, rule number) built through the program's builder from
    the same data as the reference's map; bucket ids must agree."""
    from ceph_tpu.crush import map as cm

    m = cm.CrushMap(cm.Tunables(*(crush["tunables"][k] for k in _TUNABLES)))
    m.type_names.update({1: "host", 2: "root"})
    hosts = [b for b in sorted(plain["buckets"], reverse=True)
             if plain["buckets"][b]["type"] == 1]
    for i, b in enumerate(hosts):
        got = m.make_bucket(cm.CRUSH_BUCKET_STRAW2, 1,
                            plain["buckets"][b]["items"],
                            plain["buckets"][b]["weights"], name=f"host{i}")
        if got != b:
            raise ValueError(f"program gave host bucket id {got}, not {b}")
    root = m.make_bucket(cm.CRUSH_BUCKET_STRAW2, 2, hosts,
                         plain["buckets"][plain["root"]]["weights"],
                         name="default")
    if root != plain["root"]:
        raise ValueError(f"program gave root id {root}, not {plain['root']}")
    rule = cm.Rule(0, cm.RULE_TYPE_ERASURE, 1, crush["result_max"])
    for op, a1, a2 in plain["rule"]:
        rule.step(getattr(cm, _OPS[op]), a1, a2)
    return m, m.add_rule(rule)


def run(ctx):
    from ceph_tpu.crush import mapper_jax

    from benchmark.run import Result

    t, crush = ctx.traffic, ctx.config["crush"]
    plain = ref.build_map(crush)
    cmap, ruleno = program_map(crush, plain)
    if not mapper_jax.supports(cmap, ruleno):
        raise ValueError("the vectorized mapper does not take this rule")
    width, n = crush["result_max"], t["inputs_per_call"]

    def vec_do_rule(xs):
        return mapper_jax.vec_do_rule(cmap, ruleno, xs, width)

    vec_do_rule.program = (cmap, ruleno, width)  # for the control
    do_rule = ctx.under_test("crush", vec_do_rule)
    rng = np.random.default_rng(ctx.seed)
    start = int(rng.integers(0, 1 << 31))
    do_rule(np.arange(n, dtype=np.uint32) + np.uint32(start ^ (1 << 31)))
    outs = []
    with ctx.window() as w:
        while not w.done():
            xs = np.arange(n, dtype=np.uint32) + np.uint32(start + len(outs) * n)
            with ctx.span("crush_call"):
                outs.append(np.asarray(do_rule(xs)))
    ctx.read_memory_peak()
    mapped = len(outs) * n
    pick = np.random.default_rng([ctx.seed, 3])
    idx = np.sort(pick.choice(mapped, size=min(t["check_inputs"], mapped),
                              replace=False))
    want = ref.do_rule_many(plain, ref.Lanes(plain),
                            (start + idx) & ref.M32, width)
    got = np.stack([outs[j // n][j % n] for j in idx]).astype(np.int64)
    rows = (got != want).any(axis=1)
    wrong = int(rows.sum())
    for j in idx[rows][:5]:
        ctx.note(f"x={(start + int(j)) & ref.M32}: mapped "
                 f"{list(outs[j // n][j % n])}, reference "
                 f"{list(want[np.searchsorted(idx, j)])}")
    return Result(
        e2e={"mappings_per_s": mapped / w.elapsed},
        attempted=len(outs), failed=0,
        checks={"mappings_wrong": (wrong, 0)},
        work={"calls": len(outs), "inputs": mapped},
    )
