"""Benchmark CLI, sweep, and parity non-regression corpus checks.

The corpus check is the framework's analog of the reference's
ceph-erasure-code-corpus gate (reference:src/test/erasure-code/
ceph_erasure_code_non_regression.cc:226): any kernel/matrix change that
alters output bytes fails here.
"""

import json
import pathlib
import subprocess
import sys

import pytest

from ceph_tpu.tools import ec_benchmark, ec_non_regression

CORPUS = pathlib.Path(__file__).parent / "golden" / "ec_corpus"


class TestBenchmarkCLI:
    def run_cli(self, *argv):
        import os

        env = dict(os.environ)
        env["CEPH_TPU_NO_JIT"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-m", "ceph_tpu.tools.ec_benchmark", *argv],
            capture_output=True, text=True,
            cwd=str(pathlib.Path(__file__).parent.parent), env=env,
        )
        assert out.returncode == 0, out.stderr
        return out.stdout.strip()

    def test_encode_output_format(self):
        line = self.run_cli(
            "--plugin", "jerasure", "--parameter", "k=2", "--parameter", "m=1",
            "--parameter", "technique=reed_sol_van",
            "--workload", "encode", "--size", "4096", "--iterations", "3",
        )
        seconds, kib = line.split("\t")
        assert float(seconds) > 0
        assert int(kib) == 4096 * 3 // 1024

    def test_decode_random_erasures(self):
        line = self.run_cli(
            "--plugin", "jerasure", "--parameter", "k=4", "--parameter", "m=2",
            "--parameter", "technique=reed_sol_van",
            "--workload", "decode", "--size", "4096", "--iterations", "4",
            "--erasures", "2",
        )
        seconds, kib = line.split("\t")
        assert int(kib) == 16

    def test_decode_exhaustive_inprocess(self):
        args = ec_benchmark.parse_args([
            "--plugin", "jerasure", "--parameter", "k=2", "--parameter", "m=1",
            "--parameter", "technique=reed_sol_van",
            "--workload", "decode", "--size", "2048", "--iterations", "3",
            "--erasures", "1", "--erasures-generation", "exhaustive",
        ])
        from ceph_tpu.models import registry
        codec = registry.instance().factory(
            "jerasure", ec_benchmark.make_profile(args.parameter))
        elapsed, total = ec_benchmark.run_decode(codec, args)
        assert total == 2048 * 3

    def test_batched_encode(self):
        args = ec_benchmark.parse_args([
            "--plugin", "isa", "--parameter", "k=8", "--parameter", "m=3",
            "--workload", "encode", "--size", "8192", "--iterations", "2",
            "--batch", "4",
        ])
        from ceph_tpu.models import registry
        codec = registry.instance().factory(
            "isa", ec_benchmark.make_profile(args.parameter))
        elapsed, total = ec_benchmark.run_encode(codec, args)
        assert total == 8192 * 2 * 4

    def test_bad_parameter_rejected(self):
        with pytest.raises(SystemExit):
            ec_benchmark.make_profile(["notkv"])


class TestSweep:
    def test_quick_sweep_cells(self, capsys):
        from ceph_tpu.tools import bench_sweep
        bench_sweep.main(["--quick", "--size", "2048", "--workloads", "encode"])
        lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
        # 2 plugins x 2 techniques x 2 k-values x 1 workload
        assert len(lines) == 8
        for cell in lines:
            assert "error" not in cell, cell
            assert cell["gbps"] > 0


class TestNonRegressionCorpus:
    def test_corpus_exists(self):
        assert CORPUS.is_dir()
        assert len(list(CORPUS.iterdir())) >= 10

    @pytest.mark.parametrize(
        "d", sorted(p for p in CORPUS.iterdir() if p.is_dir()),
        ids=lambda d: d.name
    )
    def test_parity_bytes_stable(self, d):
        ec_non_regression.check(d)

    def test_check_detects_regression(self, tmp_path):
        # corrupt a copied corpus entry; check must fail
        import shutil

        src = CORPUS / "jerasure-4096-k=2-m=1-technique=reed_sol_van"
        dst = tmp_path / src.name
        shutil.copytree(src, dst)
        manifest = json.loads((dst / "manifest.json").read_text())
        import base64

        chunk = bytearray(base64.b64decode(manifest["chunks"]["2"]))
        chunk[0] ^= 0xFF
        manifest["chunks"]["2"] = base64.b64encode(bytes(chunk)).decode()
        (dst / "manifest.json").write_text(json.dumps(manifest))
        with pytest.raises(SystemExit, match="differ"):
            ec_non_regression.check(dst)


# -- rados CLI + osdmaptool ---------------------------------------------------


def test_osdmaptool_roundtrip(tmp_path, capsys):
    from ceph_tpu.tools import osdmaptool

    mp = str(tmp_path / "map.json")
    assert osdmaptool.main(["--createsimple", "6", "-o", mp]) == 0
    assert osdmaptool.main([mp, "--print"]) == 0
    out = capsys.readouterr().out
    assert "max_osd 6" in out
    # add a pool offline, then map pgs and one object
    import json

    from ceph_tpu.osd.osdmap import OSDMap

    m = OSDMap.from_dict(json.load(open(mp)))
    pool = m.create_replicated_pool("data", size=3)
    json.dump(m.to_dict(), open(mp, "w"))
    assert osdmaptool.main([mp, "--test-map-pgs", "--pool", str(pool.id)]) == 0
    out = capsys.readouterr().out
    assert "pg_count 8" in out
    assert osdmaptool.main(
        [mp, "--test-map-object", "thing", "--pool", str(pool.id)]
    ) == 0
    out = capsys.readouterr().out
    assert "primary osd." in out
    out2 = str(tmp_path / "out.json")
    assert osdmaptool.main([mp, "--mark-out", "2", "-o", out2]) == 0
    m2 = OSDMap.from_dict(json.load(open(out2)))
    assert not m2.is_in(2)


def test_rados_cli_end_to_end(tmp_path, capsys):
    """put/get/ls/stat/xattr/scrub/rm through the operator CLI against a
    live mini-cluster (reference:src/tools/rados/rados.cc verbs)."""
    import asyncio

    from ceph_tpu.rados import MiniCluster
    from ceph_tpu.tools import rados_cli

    async def main():
        async with MiniCluster(n_osds=3) as cluster:
            mon = cluster.mon.addr
            loop = asyncio.get_running_loop()

            def cli(*argv):
                # the CLI owns its own event loop; run it in a thread
                return rados_cli.main(["-m", mon, *argv])

            run = lambda *a: loop.run_in_executor(None, cli, *a)  # noqa: E731
            assert await run("mkpool", "data", "erasure") == 0
            assert await run("lspools") == 0
            assert "data" in capsys.readouterr().out
            src = tmp_path / "in.bin"
            src.write_bytes(b"cli payload" * 100)
            assert await run("-p", "data", "put", "obj1", str(src)) == 0
            dst = tmp_path / "out.bin"
            assert await run("-p", "data", "get", "obj1", str(dst)) == 0
            assert dst.read_bytes() == src.read_bytes()
            assert await run("-p", "data", "ls") == 0
            assert "obj1" in capsys.readouterr().out
            assert await run("-p", "data", "stat", "obj1") == 0
            assert "size 1100" in capsys.readouterr().out
            assert await run("-p", "data", "setxattr", "obj1", "k", "v") == 0
            assert await run("-p", "data", "listxattr", "obj1") == 0
            assert "k" in capsys.readouterr().out
            assert await run("-p", "data", "scrub") == 0
            assert "0 errors" in capsys.readouterr().out
            assert await run("-p", "data", "rm", "obj1") == 0
            assert await run("-p", "data", "ls") == 0
            assert "obj1" not in capsys.readouterr().out

    asyncio.run(main())


def test_rados_cli_omap_verbs(capsys):
    """listomapkeys/listomapvals/getomapval/setomapval/rmomapkey
    (reference:src/tools/rados/rados.cc omap verbs) — omap rides
    replicated pools only."""
    import asyncio

    from ceph_tpu.rados import MiniCluster
    from ceph_tpu.tools import rados_cli

    async def main():
        async with MiniCluster(n_osds=3) as cluster:
            mon = cluster.mon.addr
            loop = asyncio.get_running_loop()

            def cli(*argv):
                return rados_cli.main(["-m", mon, *argv])

            run = lambda *a: loop.run_in_executor(None, cli, *a)  # noqa: E731
            assert await run("mkpool", "meta", "replicated") == 0
            cl = await cluster.client()
            io = cl.io_ctx("meta")
            await io.write_full("obj", b"x")
            capsys.readouterr()
            assert await run("-p", "meta", "setomapval", "obj",
                             "alpha", "1") == 0
            assert await run("-p", "meta", "setomapval", "obj",
                             "beta", "2") == 0
            assert await run("-p", "meta", "listomapkeys", "obj") == 0
            out = capsys.readouterr().out
            assert out.splitlines()[-2:] == ["alpha", "beta"]
            assert await run("-p", "meta", "getomapval", "obj",
                             "beta") == 0
            assert capsys.readouterr().out.endswith("2")
            assert await run("-p", "meta", "listomapvals", "obj") == 0
            out = capsys.readouterr().out
            assert "alpha (1 bytes):" in out and "beta (1 bytes):" in out
            assert await run("-p", "meta", "rmomapkey", "obj",
                             "alpha") == 0
            assert await run("-p", "meta", "listomapkeys", "obj") == 0
            assert "alpha" not in capsys.readouterr().out
            # missing key is a clean error, not a traceback
            assert await run("-p", "meta", "getomapval", "obj",
                             "ghost") == 1

    asyncio.run(main())


def test_ceph_osd_tree(capsys):
    """`ceph osd tree` renders the CRUSH hierarchy with status and
    weights (reference:OSDMonitor 'osd tree')."""
    import asyncio

    from ceph_tpu.rados import MiniCluster
    from ceph_tpu.tools import ceph_cli

    async def main():
        async with MiniCluster(
            n_osds=4, crush_hosts=[[0, 1], [2, 3]]
        ) as cluster:
            mon = cluster.mon.addr
            await cluster.kill_osd(3)
            await cluster.wait_for_osd_down(3)
            loop = asyncio.get_running_loop()
            rc = await loop.run_in_executor(
                None, ceph_cli.main, ["-m", mon, "osd", "tree"]
            )
            assert rc == 0
            out = capsys.readouterr().out
            lines = out.splitlines()
            assert lines[0].split() == [
                "ID", "CLASS", "WEIGHT", "TYPE", "NAME", "STATUS",
                "REWEIGHT",
            ]
            assert sum("host" in ln for ln in lines) == 2
            assert any("osd.3" in ln and "down" in ln for ln in lines)
            assert any("osd.0" in ln and "up" in ln for ln in lines)

    asyncio.run(main())


def test_ceph_osd_map(capsys):
    """`ceph osd map <pool> <obj>` agrees with the client's own
    mapping (reference:OSDMonitor 'osd map')."""
    import asyncio

    from ceph_tpu.rados import MiniCluster
    from ceph_tpu.tools import ceph_cli

    async def main():
        async with MiniCluster(n_osds=3) as cluster:
            mon = cluster.mon.addr
            cl = await cluster.client()
            await cl.create_pool("data", "replicated", size=3)
            pool = cl.osdmap.lookup_pool("data")
            pg, acting, primary = cl.osdmap.object_to_acting(
                "thing", pool.id
            )
            loop = asyncio.get_running_loop()
            rc = await loop.run_in_executor(
                None, ceph_cli.main,
                ["-m", mon, "osd", "map", "data", "thing"],
            )
            assert rc == 0
            out = capsys.readouterr().out
            assert f"({pg})" in out
            assert f"p{primary}" in out
            assert str(acting) in out
            # unknown pool is a clean error
            rc = await loop.run_in_executor(
                None, ceph_cli.main,
                ["-m", mon, "osd", "map", "nope", "thing"],
            )
            assert rc == 1

    asyncio.run(main())


def test_rados_cppool(capsys):
    """`rados cppool` copies data + xattrs + omap between pools
    (reference:rados.cc do_copy_pool)."""
    import asyncio

    from ceph_tpu.rados import MiniCluster
    from ceph_tpu.tools import rados_cli

    async def main():
        async with MiniCluster(n_osds=3) as cluster:
            mon = cluster.mon.addr
            cl = await cluster.client()
            await cl.create_pool("a", "replicated")
            await cl.create_pool("b", "replicated")
            io = cl.io_ctx("a")
            await io.write_full("o1", b"one")
            await io.write_full("o2", b"two")
            await io.setxattr("o1", "k", b"v")
            await io.omap_set("o2", {"mk": b"mv"})
            loop = asyncio.get_running_loop()
            rc = await loop.run_in_executor(
                None, rados_cli.main, ["-m", mon, "cppool", "a", "b"]
            )
            assert rc == 0
            assert "copied 2 object(s)" in capsys.readouterr().out
            dio = cl.io_ctx("b")
            assert await dio.read("o1") == b"one"
            assert await dio.getxattr("o1", "k") == b"v"
            assert (await dio.omap_get("o2"))["mk"] == b"mv"

    asyncio.run(main())
