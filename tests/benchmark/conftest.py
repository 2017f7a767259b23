"""A benchmark root that also holds the cells kept out of BENCHMARK.json
for now (``crush_cell.json``): their surfaces, references and controls
are still tested, as a cell added as files only.  And the compile-cache
settings a run makes in this process, undone after each test."""

import json
import os
import pathlib
import shutil

import pytest

ROOT = pathlib.Path(__file__).parents[2]


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    extra = json.loads((pathlib.Path(__file__).parent / "crush_cell.json")
                       .read_text())
    if extra["workload"]["name"] in {w["name"] for w in bench["workloads"]}:
        return str(ROOT)
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench["workloads"].append(extra["workload"])
    bench["end_to_end"].append(extra["end_to_end"])
    bench["per_layer"] += extra["per_layer"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """``run.configure_cache`` points JAX's persistent cache at the
    benchmark root for the rest of the process; later tests in the worker
    must not write every compile there."""
    import jax
    from jax._src import compilation_cache

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    yield
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
