"""The main path's device programs, compiled for a described v5e.

Nothing runs: each test compiles one program at its real size for a TPU
that is described, not attached, and reads what the chip's compiler
made of it.  The topology is described inside a fixture (only the
worker that runs this file loads the TPU library), and the persistent
compile cache is off around the compiles (an entry written here cannot
be read back without a chip).
"""

import os

import numpy as np
import pytest

K, M, W = 8, 3, 8
RS_LANES = (64 << 20) // K // 4  # 64 MiB of data as u32 lanes per row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    def cache(on: bool) -> None:
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()

    cache(False)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        cache(True)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    cache(True)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _u32(shape, sharding):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _compile(fn, *specs):
    import jax

    return jax.jit(fn).lower(*specs).compile().as_text()


def test_rs83_pallas_encode_64mib(one_chip):
    from ceph_tpu.ops import gf_pallas
    from ceph_tpu.ops import matrices as mx

    fn = gf_pallas.make_gf_matmul_pallas(mx.rs_vandermonde(K, M, W), W)
    hlo = _compile(fn, _u32((K, RS_LANES), one_chip))
    assert "tpu_custom_call" in hlo


def test_rs83_pallas_decode_64mib(one_chip):
    """The recovery-matrix kernel for three lost data chunks."""
    from ceph_tpu.models import registry
    from ceph_tpu.ops import gf_pallas

    codec = registry.instance().factory(
        "isa", {"technique": "reed_sol_van", "k": "8", "m": "3"})
    present, missing = tuple(range(3, 11)), (0, 1, 2)
    RM, _ = codec._recovery_matrix(present, missing)
    fn = gf_pallas.make_gf_matmul_pallas(RM, W)
    hlo = _compile(fn, _u32((K, RS_LANES), one_chip))
    assert "tpu_custom_call" in hlo


def test_cauchy_good_bitmatrix_kernel(one_chip):
    from ceph_tpu.models import registry
    from ceph_tpu.ops import gf_pallas

    codec = registry.instance().factory(
        "jerasure", {"technique": "cauchy_good", "k": "10", "m": "4",
                     "w": "8", "packetsize": "4096"})
    bm = np.asarray(codec.bitmatrix)
    lanes = 64 * gf_pallas.BLOCK
    hlo = _compile(gf_pallas.make_bitmatrix_matmul_pallas(bm),
                   _u32((bm.shape[1], lanes), one_chip))
    assert "tpu_custom_call" in hlo


def test_crush_ec_rule_program_one_launch(one_chip):
    """One launch of the EC(8+3) chooseleaf-indep program on the
    1024-OSD map that ``crushtool --test`` runs over 2^20 inputs: a
    chunk of ``X_CHUNK`` lanes must fit the chip's 16 GB of HBM (all
    2^20 lanes at once need 37.5 GB)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.crush import mapper_jax_hier as hier
    from ceph_tpu.crush.mapper_jax import X_CHUNK
    from ceph_tpu.osd.churn import synthetic_map

    osdmap = synthetic_map(1024, 16, replicated=None, ec=(
        {"plugin": "isa", "k": "8", "m": "3"}, 256))
    cmap = osdmap.crush
    pool = next(iter(osdmap.pools.values()))
    rule = cmap.find_rule(pool.crush_ruleset, pool.type, pool.size)
    take, chooses, tries, leaf_tries, _vary_r, _stable = \
        hier._rule_shape(cmap, rule)
    assert len(chooses) == 1
    T = hier.tables_for(cmap)
    spec = lambda a, dt=None: jax.ShapeDtypeStruct(  # noqa: E731
        np.shape(a), dt or a.dtype, sharding=one_chip)
    lowered = hier.choose_indep_hier.lower(
        tuple(spec(a) for a in T.tree()),
        jax.ShapeDtypeStruct((X_CHUNK,), jnp.uint32, sharding=one_chip),
        T.row_of[take],
        jax.ShapeDtypeStruct((cmap.max_devices,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        numrep=pool.size, out_size=pool.size, tries=tries,
        recurse_tries=leaf_tries or 1, want_type=chooses[0].arg2,
        leaf=True, max_depth=T.depth,
    )
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < (8 << 30)


@pytest.mark.parametrize("program", ["encode", "reconstruct"])
def test_mesh_ec_step_four_chips(topo, monkeypatch, program):
    """The mesh lane's programs over the four described chips, with the
    router steered to the TPU engine as on the chip: the reconstruct
    all-gathers the survivors over ICI."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ceph_tpu.models import registry
    from ceph_tpu.ops import gf_pallas
    from ceph_tpu.parallel.engine import MeshEcEngine

    monkeypatch.setattr(gf_pallas, "on_tpu", lambda: True)
    codec = registry.instance().factory(
        "isa", {"technique": "reed_sol_van", "k": "8", "m": "3"})
    eng = MeshEcEngine(devices=topo.devices[:4])
    mesh, pg, shard = eng.mesh_for(K)
    assert pg * shard == 4
    S, C = 64, 128 << 10  # 64 x 1 MiB stripes
    if program == "encode":
        step = eng._build_encode(codec, mesh, M)
        spec = jax.ShapeDtypeStruct(
            (S, K, C), jnp.uint8,
            sharding=NamedSharding(mesh, P(("pg", "shard"), None, None)))
    else:
        use, missing = list(range(1, 9)), [0]
        step = eng._build_reconstruct(codec, mesh, use, missing,
                                      "shard", K)
        spec = jax.ShapeDtypeStruct(
            (K, S * C), jnp.uint8,
            sharding=NamedSharding(mesh, P("shard", "pg")))
    hlo = step.lower(spec).compile().as_text()
    assert "tpu_custom_call" in hlo
    if program == "reconstruct":
        assert "all-gather" in hlo or "all_gather" in hlo
