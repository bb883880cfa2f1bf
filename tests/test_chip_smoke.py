"""chip_smoke.py on the CPU: its phases at a tiny size (Pallas kernels in
interpret mode), its refusal to report off a TPU, and the compile-cache
helper it shares with ``nmf_run``."""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
N_TERMS, N_DOCS, K, ITERS = 640, 384, 5, 3


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def tiny_budgets(smoke, monkeypatch):
    # the PubMed budgets would leave a 640 x 384 corpus unenforced
    monkeypatch.setattr(smoke, "T_U", 600)
    monkeypatch.setattr(smoke, "T_V", 250)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one_chip", "four_chips"])
def test_main_refuses_non_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_script_alone_exits_nonzero(tmp_path):
    """A directory holding only the script: no result line, non-zero exit."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_phases_tiny(smoke, tiny_budgets):
    a = smoke.make_corpus(0, N_TERMS, N_DOCS)
    model, op, fit = smoke.phase_fit(a, K, ITERS, 0)
    assert fit["warm_compiles"] == 0
    assert fit["step_s"] > 0
    # off the chip the kernels run in interpret mode: no TPU launch
    assert not smoke.kernels_compiled(op, model.v_)
    check = smoke.phase_check(a, op, model, K, ITERS, 0)
    assert len(check["error"]) == len(check["error_reference"]) == ITERS
    assert check["max_error_deviation"] <= smoke.ERROR_TOL
    assert max(check["product_deviation"].values()) <= smoke.PRODUCT_TOL
    serve = smoke.phase_serve(model, a, 8, 4)
    assert serve["served"] == 8 and serve["ticks"] == 2
    assert serve["topics_compared"] > 0


def test_phase_check_catches_a_wrong_kernel(smoke, tiny_budgets, monkeypatch):
    """A product that is off by more than the tolerance fails the check."""
    from repro.backend import get_backend

    a = smoke.make_corpus(1, N_TERMS, N_DOCS)
    model, op, _ = smoke.phase_fit(a, K, ITERS, 0)
    be = get_backend("pallas-bsr")
    real = be.matmul_with_gram
    monkeypatch.setattr(be, "matmul_with_gram",
                        lambda a, v: (lambda y, g: (y * 1.001, g))(*real(a, v)))
    with pytest.raises(smoke.SmokeError, match="AV"):
        smoke.phase_check(a, op, model, K, ITERS, 0)


@pytest.mark.parametrize("stall", ["error", "factors"])
def test_phase_check_catches_a_stalled_fit(smoke, tiny_budgets, stall):
    """A fit that stops moving one iteration early fails the check, whether
    it shows in the error trace or only in the fitted factors."""
    a = smoke.make_corpus(1, N_TERMS, N_DOCS)
    model, op, _ = smoke.phase_fit(a, K, ITERS, 0)
    if stall == "error":
        err = model.result_.error
        model.result_ = dataclasses.replace(model.result_,
                                            error=err.at[-1].set(err[-2]))
        match = "error trajectory"
    else:
        early, _, _ = smoke.phase_fit(a, K, ITERS - 1, 0)
        model.u_, model.v_ = early.u_, early.v_
        match = "fitted"
    with pytest.raises(smoke.SmokeError, match=match):
        smoke.phase_check(a, op, model, K, ITERS, 0)


def test_phase_mesh_tiny():
    """The --four-chips phase on four virtual CPU devices: the 2x2 mesh
    trajectory matches the 1x1 one."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke as s
        s.T_U, s.T_V = 600, 250
        a = s.make_corpus(0, {N_TERMS}, {N_DOCS})
        runs = s.phase_mesh(a, {K}, {ITERS}, 0)
        print(json.dumps({{k: v["devices"] for k, v in runs.items()}}))
    """)
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {"1x1": 1,
                                                               "2x2": 4}


@pytest.fixture()
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_nmf_run_cache_lands_in_env_dir(tmp_path):
    """The entry point's compiled programs are written where the variable
    says (every compile is cached here: no minimum compile time)."""
    cache = tmp_path / "cache"
    env = dict(os.environ, PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.nmf_run", "--small",
         "--iters", "2"], env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert any(p.name.startswith("jit_als_nmf") for p in cache.iterdir())


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache") == str(DEFAULT_CACHE_DIR)
    assert enable_compile_cache() == path
    assert jax.config.jax_compilation_cache_dir == path
    assert "/.jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
