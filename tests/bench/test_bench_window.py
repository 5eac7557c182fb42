"""The benchmark's window, correctness check and control on the CPU, at the
granite-3-8b-smoke widths: the harness's functions called directly (the
command itself refuses to run without an accelerator)."""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

import bench_cpu as smoke
from bench.lib import check, harness, serve, traffic

SECONDS = 3.0


def _measure(mix, seed, dtype=jnp.float32, trace_dir=None):
    from bench.lib import weights
    t0 = time.perf_counter()
    params = weights.make_params(smoke.MODEL, seed, dtype)
    eng = serve.build_engine(smoke.MODEL, mix, params, offload="sim",
                             kernels=False, dtype=dtype)
    plan = traffic.plan(mix, seed, smoke.MODEL["vocab_size"],
                        traffic.window_requests(mix, SECONDS))
    w = serve.run(eng, mix, plan, SECONDS, t0, trace_seconds=1.0,
                  trace_dir=trace_dir)
    return w, params, {p.index: p.prompt for p in plan}


@pytest.fixture(scope="module")
def closed_run():
    return _measure(smoke.mix("closed"), 2**33 + 12345)


@pytest.mark.parametrize("kind", ["closed", "open", "open-lead"])
def test_window_counts_and_compiles(kind, closed_run, tmp_path):
    mix = smoke.mix(kind.split("-")[0])
    if kind == "open-lead":
        mix["lead_s"] = 1.5
    w, params, prompts = closed_run if kind == "closed" else \
        _measure(mix, 77, trace_dir=str(tmp_path))
    if kind == "open":                  # profiled; the CPU has no device
        from bench.lib import trace     # plane, so nothing to reduce
        assert list(tmp_path.rglob("*.xplane.pb"))
        assert trace.load(str(tmp_path)) is None
    assert w.compiles == 0
    assert w.t_end - w.t_start == pytest.approx(SECONDS)
    assert w.setup_s > 0
    ok, attempted, failed, checks = harness.judge(
        w, params, prompts, smoke.MODEL, mix, {"logit_gap_max": 1e-3}, 5)
    due = [r for r in w.records.values() if w.t_start <= r.due < w.t_end]
    lead = [r for r in w.records.values() if r.due < w.t_start]
    if kind == "closed":
        assert attempted == len(due) + mix["clients"] >= mix["clients"]
    else:
        # the lead-in's requests were sent in set-up, before the window
        assert attempted == len(due) + len(lead)
        assert len(due) >= SECONDS * mix["rate_per_s"] - 2
        assert len(lead) >= mix.get("lead_s", 0) * mix["rate_per_s"] - 2
        assert all(r.submit_t < w.t_start for r in lead)
    assert failed == 0 and ok, checks
    # a float32 program agrees with the reference to rounding
    assert checks["logit_gap_max"]["value"] < 1e-3


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where the decode window produces it: the run is not
    correct."""
    from repro.serving.engine import ServeEngine
    orig = ServeEngine.decode_window

    def broken(self, state, loop):
        state, loop, toks, valid, stats, n = orig(self, state, loop)
        return state, loop, (toks + 1) % self.cfg.vocab_size, valid, \
            stats, n
    monkeypatch.setattr(ServeEngine, "decode_window", broken)
    mix = smoke.mix("closed")
    w, params, prompts = _measure(mix, 3)
    ok, _, failed, checks = harness.judge(
        w, params, prompts, smoke.MODEL, mix, {"logit_gap_max": 1e-3}, 3)
    assert failed == 0
    assert not ok and checks["logit_gap_max"]["value"] > 1e-3


def test_fp8_control_reads_wider_than_program(closed_run):
    """The control (the reference in float8) is judged against the float32
    reference on the program's own prompts and tokens, and reads a wider
    gap than the program."""
    w, params, prompts = closed_run
    mix = smoke.mix("closed")
    reqs = check.sample(w.records, 2, 1)
    prog = max(float(np.max(g)) for g in check.logit_gaps(
        params, smoke.MODEL, mix, reqs, prompts))
    ctl = max(float(np.max(g)) for g in check.logit_gaps(
        params, smoke.MODEL, mix, reqs, prompts, quant="fp8"))
    assert ctl > 0.1 > 1e-3 > prog


def test_new_traffic_file_is_found_by_name(tmp_path):
    """A mix added as a data file is read by the one generator, no edit."""
    mix = dict(smoke.mix("open"), rate_per_s=3.0, requests=8)
    (tmp_path / "burst-chat.json").write_text(json.dumps(mix))
    got = traffic.load("burst-chat", directory=tmp_path)
    plan = traffic.plan(got, 9, 1024)
    assert len(plan) == 8
    assert all(150 <= len(p.prompt) <= 300 for p in plan)
    assert all(20 <= p.max_new_tokens <= 40 for p in plan)
    assert np.all(np.diff([p.due_s for p in plan]) > 0)
    with pytest.raises(FileNotFoundError):
        traffic.load("no-such-mix", directory=tmp_path)
