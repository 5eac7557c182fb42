"""The benchmark's reductions, on small synthesized records and traces:
busy union and idle share, percentiles over all requests, rates over the
window only, roofline shares against hand counts, the peaks table, and the
consistency of ``BENCHMARK.json`` with the files it names."""
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_cpu as smoke
from bench.lib import harness, peaks, serve, stats, trace, traffic, weights

PEAK = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}


def ev(name, start, end):
    return trace.Event(name, start, end)


def synthetic_trace():
    """Two decode windows of two steps over 2 layers, on one device, in a
    10 s traced window; one prefill module without paged attention."""
    ops, mods = [], []
    t = 1.0
    for _ in range(2):                                  # decode modules
        m0 = t
        for _ in range(2 * 2):                          # steps x layers
            ops.append(ev("%paged_attention.3 = bf16[8] custom-call(%a)",
                          t, t + 0.1))
            ops.append(ev("%fusion.7 = bf16[8] fusion(%paged_attention.3)",
                          t + 0.1, t + 0.3))
            ops.append(ev("%recall_gather_host.1 = u32[8] custom-call()",
                          t + 0.3, t + 0.35))
            t += 0.35
        mods.append(ev("jit__lambda(1)", m0, t))
        t += 0.5                                        # host gap
    ops.append(ev("%while.1 = (s32[]) while(%t)", 1.0, 2.4))   # container
    mods.append(ev("jit__lambda(2)", 6.0, 7.0))          # prefill
    ops.append(ev("%convolution.2 = bf16[8] convolution()", 6.0, 7.0))
    host = [ev("bench.traced_window", 0.0, 10.0),
            ev("$scheduler.py:621 _window_steps", 0.0, 10.0),
            ev("$engine.py:420 prefill_one", 2.4, 2.9)]
    return trace.Trace((0.0, 10.0), {"/device:TPU:0": ops},
                       {"/device:TPU:0": mods}, host)


def test_busy_union_and_idle_share():
    assert trace.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)], 0, 10) == 4
    assert trace.union([(0, 2), (8, 12)], 1, 10) == 3
    tr = synthetic_trace()
    # 2 x 1.4 s of decode ops and 1 s of prefill in a 10 s window
    assert trace.busy_s(tr) == pytest.approx(3.8)
    from bench.metrics import device_idle_pct
    run = harness.Run(smoke.MODEL, smoke.mix("closed"), None, tr, PEAK)
    assert device_idle_pct.read(run) == pytest.approx(62.0)
    gaps = trace.gaps(tr)
    assert gaps[0] == pytest.approx((7.0, 10.0))
    assert trace.top_gaps(tr)[3][0] == "$engine.py:420 prefill_one"


def test_idle_inside_a_loop_shows():
    """A gap between the operations of a ``while`` body is idle time: the
    loop, which encloses its body, counts for neither busy time nor gaps."""
    ops = [ev("%while.4 = (s32[]) while(%t)", 1.0, 5.0),
           ev("%fusion.1 = bf16[8] fusion(%a)", 1.0, 2.0),
           ev("%fusion.2 = bf16[8] fusion(%b)", 3.5, 5.0)]
    tr = trace.Trace((0.0, 10.0), {"/device:TPU:0": ops}, {},
                     [ev("bench.traced_window", 0.0, 10.0),
                      ev("$scheduler.py:633 _window_steps", 2.0, 3.5)])
    assert trace.busy_s(tr) == pytest.approx(2.5)
    assert trace.gaps(tr) == [pytest.approx(g) for g in
                              ((5.0, 10.0), (2.0, 3.5), (0.0, 1.0))]
    assert trace.top_gaps(tr)[1][0] == "$scheduler.py:633 _window_steps"


def test_roofline_counts_the_staging_of_vmem_operands():
    """An operand placed in VMEM (``S(1)``) was read from HBM by the
    operation that produced it: that operation's time is the kernel's."""
    from bench.kernels import paged_attention
    kv = "bf16[4,2,72,64]{3,2,1,0:T(8,128)(2,1)S(1)}"
    call = ("%paged_attention.11 = bf16[4,2,2,64]{3,2,1,0:T(4,128)S(1)} "
            f"custom-call(s32[4]{{0:T(128)S(1)}} %bitcast.4, {kv} "
            f"%pad_fusion.11, {kv} %pad_fusion.12, s32[4]{{0}} %pos.1), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace.staged_operands(trace.Event(call, 0, 1)) == [
        "bitcast.4", "pad_fusion.11", "pad_fusion.12"]
    ops, t = [], 1.0
    for _ in range(3):
        ops += [ev(f"%pad_fusion.11 = {kv} fusion(%x)", t, t + 0.2),
                ev(f"%pad_fusion.12 = {kv} fusion(%y)", t + 0.2, t + 0.4),
                ev("%pos.1 = s32[4]{0} fusion(%z)", t + 0.4, t + 0.45),
                ev(call, t + 0.45, t + 0.5)]
        t += 1.0
    # the first call's K producer ran before the traced window opened
    tr = trace.Trace((1.1, 10.0), {"/device:TPU:0": ops}, {}, [])
    calls = trace.kernel_calls(tr, "paged_attention")
    assert len(calls) == 2
    assert [p.op for p in calls[0][1]] == ["pad_fusion.11", "pad_fusion.12"]
    mix, m = smoke.mix("closed"), smoke.MODEL
    run = harness.Run(m, mix, None, tr, PEAK)
    fl, by = paged_attention.counts(m, mix)
    t_min = max(fl / PEAK["bf16_flops_per_s"], by / PEAK["hbm_bytes_per_s"])
    # kernel 0.05 s plus its two staging fusions 0.2 s each, per call
    assert stats.roofline_pct(run, "paged_attention") == pytest.approx(
        100 * t_min / 0.45)


def test_kernels_match_by_instruction_name():
    tr = synthetic_trace()
    # the fusion that reads paged_attention's output is not the kernel
    assert len(trace.kernel_events(tr, "paged_attention")) == 8
    assert len(trace.kernel_events(tr, "paged")) == 0
    names = [n for n, _ in trace.top_ops(tr)]
    assert "while.1" not in names and names[0] == "fusion.7"
    assert len(trace.modules_with(tr, "paged_attention")) == 2


def test_decode_step_and_host_dma_per_step():
    from bench.metrics import decode_step_ms, host_dma_ms_per_step
    run = harness.Run(smoke.MODEL, smoke.mix("closed"), None,
                      synthetic_trace(), PEAK)
    # 2 modules of 1.4 s, 4 steps (8 paged_attention calls / 2 layers)
    assert decode_step_ms.read(run) == pytest.approx(700.0)
    assert host_dma_ms_per_step.read(run) == pytest.approx(100.0)


def test_roofline_against_hand_counts():
    from bench.kernels import page_scores, paged_attention
    mix = smoke.mix("closed")          # slots 4, page 8, budget 64, 8 + 8
    m = smoke.MODEL                    # 4 heads over 2 KV heads, d 64
    L = 8 + 8 + 8 + 6 * 8              # sink, ring (window + page), 6 pages
    assert paged_attention.resident_tokens(mix) == L
    fl, by = paged_attention.counts(m, mix)
    assert fl == 2 * 2 * 4 * 4 * L * 64
    assert by == 2 * 4 * 2 * L * 64 * 2 + 4 * 2 * L * 4 + 2 * 4 * 4 * 64 * 2
    n_pages = -(-traffic.max_len(mix) // 8)
    fl, by = page_scores.counts(m, mix)
    assert fl == 2 * 2 * 4 * 4 * n_pages * 64
    assert by == 4 * n_pages * 2 * 2 * 64 * 2 + 4 * 4 * 64 * 2 \
        + 4 * 4 * n_pages * 4
    run = harness.Run(m, mix, None, synthetic_trace(), PEAK)
    fl, by = paged_attention.counts(m, mix)
    t_min = max(fl / PEAK["bf16_flops_per_s"], by / PEAK["hbm_bytes_per_s"])
    got = stats.roofline_pct(run, "paged_attention")
    assert got == pytest.approx(100 * t_min / 0.1)
    assert stats.roofline_pct(run, "page_scores") is None   # not in trace


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def _window(records, t0=100.0, t1=110.0):
    return serve.Window({r.uid: r for r in records}, 1.0, t0, t1, 0, 0.0,
                        0.0, 0.0)


def test_percentiles_over_all_requests():
    rs = []
    for i in range(20):                       # due 100.0 .. 109.5
        r = serve.Req(i, 10, 4, due=100.0 + 0.5 * i)
        r.first_t = r.due + 0.01 * (i + 1)
        rs.append(r)
    early = serve.Req(99, 10, 4, due=99.0)     # due before the window
    early.first_t = 99.5
    rs.append(early)
    w = _window(rs)
    want = [0.01 * (i + 1) for i in range(20)]
    assert sorted(stats.ttfts(w)) == pytest.approx(want)
    from bench.metrics import ttft_ms_p50, ttft_ms_p95
    run = harness.Run(smoke.MODEL, smoke.mix("open"), w, None, PEAK)
    assert ttft_ms_p50.read(run) == pytest.approx(
        1e3 * float(np.percentile(want, 50)))
    assert ttft_ms_p95.read(run) == pytest.approx(
        1e3 * float(np.percentile(want, 95)))


def test_rate_over_the_window_only():
    """A delivery's tokens are spread over the time since the request's
    previous delivery; only the share inside the window counts."""
    r = serve.Req(1, 10, 8, due=99.0, submit_t=99.0)
    r.token_t = [99.5,                          # first token, before
                 101.0, 101.0001, 101.0002,     # one delivery of 3
                 111.0, 111.0001]               # one of 2, after the end
    w = _window([r])
    # 3 x (101 - 100) / (101 - 99.5) + 2 x (110 - 101) / (111 - 101)
    assert stats.tokens_in_window(w) == pytest.approx(2.0 + 1.8, rel=1e-3)
    from bench.metrics import output_tok_s
    run = harness.Run(smoke.MODEL, smoke.mix("open"), w, None, PEAK)
    assert output_tok_s.read(run) == pytest.approx(0.38, rel=1e-3)


def test_request_without_first_token_counts_its_wait():
    r = serve.Req(1, 10, 4, due=101.0)
    done = serve.Req(2, 10, 4, due=100.0)
    done.first_t, done.finish_t = 100.5, 115.0
    assert sorted(stats.ttfts(_window([r, done]))) == pytest.approx(
        [0.5, 14.0])


def test_every_seed_gets_the_same_sizes_and_arrivals():
    mix = dict(smoke.mix("open"), requests=8)
    a, b = traffic.plan(mix, 1, 1024), traffic.plan(mix, 2, 1024)
    assert [len(p.prompt) for p in a] == [len(p.prompt) for p in b]
    assert [p.max_new_tokens for p in a] == [p.max_new_tokens for p in b]
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)   # the seed's part
    for lo, hi in ((0, 4), (4, 8)):       # each block: the range's quantiles
        assert sorted(p.max_new_tokens for p in a[lo:hi]) == [22, 28, 32, 38]
    assert np.all(np.diff([p.due_s for p in a]) > 0)
    assert all(np.all(p.prompt > 0) for p in a)       # pad token 0 unused
    assert traffic.buckets(mix) == [192, 256, 320]


def test_weights_match_the_serving_layout_and_the_seed():
    from repro.models.model import init_params
    cfg = serve.program_config(smoke.MODEL)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0),
                                              jnp.bfloat16))
    got = jax.eval_shape(lambda: weights.make_params(smoke.MODEL, 0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(got)))
    big = 2**40 + 3
    x = weights.make_params(smoke.MODEL, big)["embed"]["tok"]
    y = weights.make_params(smoke.MODEL, big)["embed"]["tok"]
    z = weights.make_params(smoke.MODEL, big + 1)["embed"]["tok"]
    assert np.array_equal(x, y) and not np.array_equal(x, z)


def test_benchmark_json_names_files_that_exist():
    spec = json.loads(harness.SPEC.read_text())
    for c in spec["configs"]:
        model = json.loads((harness.ROOT / c["file"]).read_text())
        assert model["name"] == c["name"]
        assert set(c["reduced"]) == set(model["reduced"])
        serve.program_config(model)             # widths agree with registry
    for w in spec["workloads"]:
        mix = traffic.load(w["traffic"])
        assert mix["loop"] in ("open", "closed")
        cell, _, e2e, per_layer = harness.cell_spec(w["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert per_layer
        from bench.lib import check
        assert check.limits(w["name"])["logit_gap_max"] > 0
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(importlib.import_module(
            f"bench.metrics.{m['name']}").read)


def test_check_samples_the_longest_and_pads_like_the_engine():
    from bench.lib import check
    recs = {}
    for uid, n in enumerate([3, 9, 0, 5, 7, 1]):
        r = serve.Req(uid, 10, 9)
        r.tokens = list(range(n))
        recs[uid] = r
    a = check.sample(recs, 3, 11)
    assert a[0].uid == 1 and len(a) == 3
    assert all(r.tokens for r in a)               # uid 2 served nothing
    assert [r.uid for r in check.sample(recs, 3, 11)] == [r.uid for r in a]
    assert check.sample({}, 3, 11) == []
    p = np.array([5, 6, 7], np.int32)
    assert check.padded_prompt(p, 4).tolist() == [0, 5, 6, 7]
    assert check.padded_prompt(np.arange(1, 9, dtype=np.int32), 4
                               ).tolist() == list(range(1, 9))
