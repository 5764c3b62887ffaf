"""``chip_smoke.py``'s phases at tiny sizes on the CPU, checked against the
same references the chip run uses (the kernel runs interpreted here). The
script itself refuses any platform but a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _passed(checks):
    return bool(checks) and all(ok for _, ok in checks)


def test_graph_phase_matches_references():
    checks = _chip_smoke().graph_phase(scale=10, seed=0)
    assert [name for name, _ in checks] == ["pagerank", "sssp"]
    assert _passed(checks), checks


def test_serve_phase_matches_reference():
    from repro.configs import base as cfgs

    smoke = _chip_smoke()
    with smoke.CompileCounter() as counter:
        checks = smoke.serve_phase(cfgs.reduced(cfgs.get_arch("mind")),
                                   seed=0, counter=counter)
    assert _passed(checks), checks


@pytest.mark.parametrize("mutation", ["mlp_scaled", "mlp_dropped",
                                      "s_mat_transposed", "one_round_less"])
def test_mind_reference_sees_a_wrong_model(mutation):
    """At MIND's published widths (a short table) the float64 host
    reference agrees with ``repro.nn.recsys``, and the serve check's
    tolerance refuses each of these wrong models."""
    import dataclasses

    import numpy as np

    from repro.configs import base as cfgs
    from repro.nn import recsys

    smoke = _chip_smoke()
    cfg = dataclasses.replace(cfgs.get_arch("mind"), n_items=4096)
    params = recsys.init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    hist = rng.integers(0, cfg.n_items, (4, cfg.hist_len)).astype(np.int32)
    cand = rng.integers(0, cfg.n_items, (4, 8)).astype(np.int32)

    def model(p, c=cfg):
        return np.asarray(recsys.score_candidates(
            recsys.user_interests(p, c, hist, np.ones(hist.shape, bool)),
            recsys.table_lookup(p, cand)))

    ref = smoke.mind_scores_reference(params, cfg, hist, cand)
    np.testing.assert_allclose(model(params), ref, rtol=1e-5, atol=1e-6)
    w0, w1 = params["mlp"]
    bad = {
        "mlp_scaled": lambda: model(dict(params, mlp=[w0, {"w": w1["w"] * 1.1}])),
        "mlp_dropped": lambda: model(dict(params, mlp=[w0, {"w": w1["w"] * 0}])),
        "s_mat_transposed": lambda: model(dict(params, s_mat=params["s_mat"].T)),
        "one_round_less": lambda: model(params, dataclasses.replace(
            cfg, capsule_iters=cfg.capsule_iters - 1)),
    }[mutation]()
    err = np.abs(bad - ref) / (smoke.SCORE_ATOL + smoke.SCORE_RTOL * np.abs(ref))
    assert err.max() > 1.0


def test_grasp_phase_matches_reference_on_one_device():
    checks = _chip_smoke().grasp_phase(jax.devices()[:1], seed=0, scales=(9,))
    assert _passed(checks), checks


def test_script_refuses_a_cpu(capsys):
    """Refused before anything is set up: the compile cache stays off."""
    assert jax.devices()[0].platform == "cpu"
    cache_dir = jax.config.jax_compilation_cache_dir
    assert _chip_smoke().main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out
    assert jax.config.jax_compilation_cache_dir == cache_dir
