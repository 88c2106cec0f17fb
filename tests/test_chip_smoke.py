"""chip_smoke.py's phases on the CPU at the SMOKE config, and its refusal
to run anywhere but on a TPU inside the repository.

The script itself has no CPU mode: these tests import its phase functions
and drive them with a small workload, so its logic is checked here and its
widths on the chip."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import configs

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


cs = _load()
WL = cs.Workload(min_prompt=5, max_prompt=20, max_new=4, page_size=8,
                 n_pages=32, slots=3)


@pytest.fixture(scope="module")
def cfg():
    return configs.get_config(cs.ARCH, smoke=True)


@pytest.fixture(scope="module")
def prompts(cfg):
    return WL.prompts(cfg.vocab)


@pytest.fixture(scope="module")
def trusted(cfg):
    return cs.build_gateway(cfg, "trusted", WL)


@pytest.fixture(scope="module")
def clean(trusted, prompts):
    return cs.serve_phase(trusted, WL, prompts)


def test_workload_shape(prompts):
    assert [t for t, _ in prompts] == ["alice", "bob", "carol"] * 2
    assert all(WL.min_prompt <= len(p) <= WL.max_prompt for _, p in prompts)
    assert WL.max_pages * WL.page_size >= WL.max_prompt + WL.max_new
    full = cs.Workload()
    assert (full.n_requests, full.max_new, full.page_size, full.max_pages) \
        == (6, 16, 16, 17)


def test_trusted_gateway_holds_no_plaintext_weights(cfg, trusted):
    """build_gateway's own check ran; the engine's weights are sealed."""
    from repro.core.sealed import is_sealed
    leaves = jax.tree_util.tree_leaves(trusted.engine.params,
                                       is_leaf=is_sealed)
    assert leaves and all(is_sealed(x) for x in leaves)


def test_plaintext_check_catches_a_kept_copy(cfg, monkeypatch):
    """A plaintext copy made while building that outlives the constructor
    fails build_gateway's check."""
    make, kept = cs.init_params, []

    def init_and_keep(c):
        params = make(c)
        if not kept:                        # the real call, not eval_shape's
            kept.append(jax.tree_util.tree_map(jnp.copy, params))
        return params

    monkeypatch.setattr(cs, "init_params", init_and_keep)
    with pytest.raises(AssertionError, match="plaintext weights"):
        cs.build_gateway(cfg, "trusted", WL)


def test_serve_phase(trusted, clean):
    assert len(clean) == WL.n_requests
    assert all(len(t) == WL.max_new for t in clean)
    assert trusted.metrics()["launches_verified"] > 0


def test_tamper_phase_poisons_one_request(trusted, prompts, clean):
    cs.tamper_phase(trusted, WL, prompts, clean)


def test_off_phase_matches_trusted(cfg, prompts, clean):
    off = cs.off_phase(cs.build_gateway(cfg, "off", WL), WL, prompts,
                       clean)
    assert off == clean


def test_off_phase_catches_a_mismatch(cfg, prompts, clean):
    wrong = [list(t) for t in clean]
    wrong[2][1] = (wrong[2][1] + 1) % cfg.vocab
    with pytest.raises(AssertionError, match="request 2"):
        cs.off_phase(cs.build_gateway(cfg, "off", WL), WL, prompts,
                     wrong)


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(SCRIPT, ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "no TPU" in r.stderr


def test_refuses_outside_the_repository(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    r = _run(tmp_path / SCRIPT.name, tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "src/repro" in r.stderr
