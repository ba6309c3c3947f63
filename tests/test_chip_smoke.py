"""chip_smoke.py's phases, in-process, at a tiny N on the CPU.

The script itself refuses to run without a TPU (and these tests pin
that); its phase functions take the store and the reference columns as
arguments, so the suite drives them directly: reference comparison,
served round trip, fold, split ingest, and the ``--chips 4`` path on
four of conftest's virtual devices. What only the chip can show - that
Mosaic compiles the kernels and the TPU-only branches run - is
chip_smoke.py's own job (and tests/test_chip_compile.py's, without a
chip).
"""

import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402

SEED = 7
N = 300_000


@pytest.fixture(scope="module")
def world():
    cols = chip_smoke.Columns(N, SEED)
    queries = chip_smoke.make_queries(SEED, 9)
    ds = chip_smoke.build_store(cols)
    calls = chip_smoke.KernelCalls()
    try:
        kept = chip_smoke.embedded_queries(ds, cols, queries, calls)
        chip_smoke.fused_batch(ds, queries, kept, calls)
    finally:
        calls.close()
    return types.SimpleNamespace(cols=cols, queries=queries, ds=ds, kept=kept)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"], ["--rows", "1000"]])
def test_main_refuses_without_a_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out and out.strip() == ""


def test_a_failing_phase_fails_the_run(monkeypatch, capsys):
    """With the device check satisfied, a phase that raises ends main()
    with that exception and no last line."""
    import jax

    fake = types.SimpleNamespace(platform="tpu", device_kind="fake")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])

    def boom(*a, **kw):
        raise RuntimeError("phase failed")

    monkeypatch.setattr(chip_smoke, "build_store", boom)
    with pytest.raises(RuntimeError, match="phase failed"):
        chip_smoke.main(["--rows", "5000"])
    assert '"ok": true' not in capsys.readouterr().out


def test_reference_comparison(world, capsys):
    """The fixture ran every embedded comparison and the fused batch;
    here: the references are not vacuous, and a wrong answer is caught."""
    assert sum(len(v) for v in world.kept.values()) > 1000
    q = chip_smoke.agg_queries(world.queries, world.kept)[1]
    out = world.ds.query(chip_smoke.TYPE, q.ecql)
    want = world.kept[q.ecql]
    assert chip_smoke.check_rows(out, want, "same") == len(want) > 0
    with pytest.raises(AssertionError, match="chip_smoke"):
        chip_smoke.check_rows(out, want[1:], "one id short")
    chip_smoke.knn(world.ds, world.cols, SEED)
    assert '"phase": "knn"' in capsys.readouterr().out


def test_loose_reference_is_the_f32_one(world):
    """The aggregation reference differs from the exact one exactly where
    f32 does: same rows here, and never fewer."""
    q = chip_smoke.agg_queries(world.queries, world.kept)[1]
    x32, _ = chip_smoke.ref_loose_rows(world.cols, q)
    assert len(x32) >= len(world.kept[q.ecql])
    assert x32.dtype == np.float32


def test_served_fold_and_split_ingest(world, capsys):
    """Order matters (each phase edits the store and the reference the
    way main() does), so the three are one test."""
    from geomesa_tpu import conf

    chip_smoke.served(world.ds, world.cols, world.kept, SEED)
    assert world.ds.server is None or world.ds.server.closed
    conf.STREAM_FOLD_DEVICE.set("on")  # auto is TPU-only: run the plan here too
    try:
        chip_smoke.fold(
            world.ds, world.cols,
            chip_smoke.agg_queries(world.queries, world.kept), SEED,
        )
    finally:
        conf.STREAM_FOLD_DEVICE.clear()
    chip_smoke.split_ingest(world.ds, SEED)
    out = capsys.readouterr().out
    for phase in ("serve_query", "serve_ingest", "serve_tile", "fold", "split_ingest"):
        assert f'"phase": "{phase}"' in out
    assert '"durable": true' in out and '"device_plan": true' in out


def test_four_chip_path_on_virtual_devices(capsys):
    """--chips 4 runs the mesh phase and its reference, nothing else."""
    import jax

    assert len(jax.devices()) >= 4  # conftest forces 8 virtual CPU devices
    chip_smoke.run(1_500_000, SEED, chips=4, tile=4096)
    phases = [ln.split('"')[3] for ln in capsys.readouterr().out.splitlines() if ln]
    assert phases[:3] == ["generate", "load", "mesh_placement"]
    assert "query_many" in phases
    assert not {"link", "warmup_cold", "serve_query", "fold", "split_ingest"} & set(phases)


def test_density_check_allows_only_what_f32_can_move():
    rng = np.random.default_rng(SEED)
    x32 = rng.uniform(0, 22.5, 50_000).astype(np.float32)
    y32 = rng.uniform(0, 22.5, 50_000).astype(np.float32)
    env = (0.0, 0.0, 22.5, 22.5)
    grid = chip_smoke.ref_density_f32(x32, y32, env, 256, 256).astype(np.float32)
    info = chip_smoke.check_density(grid, x32, y32, env, 256, 256, "same")
    assert info["rows"] == 50_000 and info["pixels_differing_from_ieee_f32"] == 0
    moved = grid.copy()
    iy, ix = np.argwhere(moved > 0)[0]
    moved[iy, ix] -= 1
    moved[(iy + 7) % 256, ix] += 1  # one row, seven pixels off: sum still right
    with pytest.raises(AssertionError, match="pixels outside"):
        chip_smoke.check_density(moved, x32, y32, env, 256, 256, "moved")
    short = grid.copy()
    short[iy, ix] -= 1
    with pytest.raises(AssertionError, match="grid sums to"):
        chip_smoke.check_density(short, x32, y32, env, 256, 256, "short")


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_placement(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code. Unset:
    <checkout>/.jax_cache and nothing else."""
    import jax

    import geomesa_tpu

    updates = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(geomesa_tpu, "_cache_enabled", False)
    monkeypatch.delenv("GEOMESA_TPU_NO_COMPILE_CACHE", raising=False)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    geomesa_tpu.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(geomesa_tpu.__file__)))
    if env_dir is None:
        assert updates["jax_compilation_cache_dir"] == os.path.join(root, ".jax_cache")
    else:
        assert "jax_compilation_cache_dir" not in updates
