"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The reference gates its hardware-dependent tests (Kakadu) behind runtime
probes (reference: src/test/java/.../converters/KakaduConverterTest.java:97-115).
We do the analog for TPUs: tests always run on a virtual 8-device CPU
platform so sharding logic is exercised without real chips; the chip
runs are ``chip_smoke.py`` and the ``perfbench/`` benchmark on a TPU
host.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Default the scheduler's device pool to ONE worker for the suite:
# on the forced 8-device mesh every device a worker scales onto pays
# its own XLA recompile of the frontend program (~tens of seconds on
# this CPU probe), which any test doing concurrent encodes would
# otherwise trigger incidentally. Pool behavior is exercised
# deliberately — with explicit ``devices=`` counts — by
# tests/test_scheduler_pool.py; everything else keeps the seed's
# single-device placement and runtime.
os.environ.setdefault("BUCKETEER_SCHED_DEVICES", "1")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Persistent XLA compilation cache, resolved as the program resolves it
# (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache): the stripe-
# parallel Tier-1 programs cost ~20 s of XLA each, several tests compile
# the same (L, shape) variants, and the deviceaudit session fixture
# clears JAX's in-memory caches once (fingerprint reproducibility) —
# with the disk cache, every recompile after that is a read, and the
# next session finds them again.
from bucketeer_tpu.converters.tpu import enable_compile_cache  # noqa: E402

enable_compile_cache()

# Async HTTP-API tests (tests/test_api.py) run on aiohttp's pytest plugin.
pytest_plugins = ("aiohttp.pytest_plugin",)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(20260729)


@pytest.fixture(scope="session")
def repo_facts(tmp_path_factory):
    """One full registry lowering per test session, shared by
    test_deviceaudit and test_graftcost — run in a subprocess.
    Lowering every registered program costs ~half a minute of tracing,
    and ``deviceaudit.run_programs`` deliberately clears JAX's global
    caches first (fingerprint reproducibility): in-process that would
    force every later test's already-compiled programs to rebuild, so
    the lowering happens in its own interpreter and ships its facts
    back as a pickle (pure data: lowered text + modeled costs)."""
    import pickle
    import subprocess
    import sys

    out = tmp_path_factory.mktemp("audit") / "facts.pkl"
    script = (
        "import pickle, sys\n"
        "from bucketeer_tpu.analysis import deviceaudit\n"
        "pickle.dump(deviceaudit.run_programs(),\n"
        "            open(sys.argv[1], 'wb'))\n")
    subprocess.run([sys.executable, "-c", script, str(out)], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return pickle.loads(out.read_bytes())


@pytest.fixture(scope="session")
def mesh_facts():
    """One mesh-registry lowering per test session (test_graftmesh) —
    forced into graftmesh's subprocess path even though this
    interpreter already runs the 8-device mesh: the inline path clears
    JAX's global caches first (fingerprint reproducibility), which
    would force every later test's compiled programs to rebuild."""
    from bucketeer_tpu.analysis import graftmesh

    return graftmesh.run_mesh_programs(in_process=False)


@pytest.fixture()
def cached_mesh_lowering(mesh_facts, monkeypatch):
    """Patch graftmesh.run_mesh_programs to replay the session's mesh
    lowering — the graftmesh analog of cached_lowering below, for CLI
    tests of --mesh-audit argument handling and gating."""
    import copy

    from bucketeer_tpu.analysis import graftmesh

    def replay(entries=None, *, in_process=None):
        if entries is not None:
            raise ValueError("cached mesh lowering replays the "
                             "registry only")
        return [copy.deepcopy(f) for f in mesh_facts]

    monkeypatch.setattr(graftmesh, "run_mesh_programs", replay)
    return mesh_facts


@pytest.fixture()
def cached_lowering(repo_facts, monkeypatch):
    """Patch deviceaudit.run_programs to replay the session's lowering
    — for CLI tests that exercise argument handling and gating, not
    the lowering itself (each real invocation re-lowers the registry
    *and* nukes the compile caches the rest of the suite relies on)."""
    import copy

    from bucketeer_tpu.analysis import deviceaudit

    def replay(entries=None):
        wanted = (None if entries is None
                  else {e.name for e in entries})
        return [copy.deepcopy(f) for f in repo_facts
                if wanted is None or f.name in wanted]

    monkeypatch.setattr(deviceaudit, "run_programs", replay)
    return repo_facts
