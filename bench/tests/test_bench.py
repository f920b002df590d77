"""CPU tests of the chip benchmark: the trace reduction, the operation and
byte counts, the traffic generator, the result line, the refusal without a
chip, and the comparison that decides ``correct`` with the timed path broken.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

Nothing here loads a TPU library: the cells run at a tiny size on the CPU
(``bench/testdata``) with the look for a chip skipped.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "testdata"
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import ops  # noqa: E402
import devtrace  # noqa: E402
import traffic  # noqa: E402

STABLELM = json.loads((BENCH / "configs" / "stablelm-12b.w12.json").read_text())
# rwkv6-3b at its published widths (the cell itself waits for chip readings)
RWKV = dict(json.loads((DATA / "bench" / "configs" / "tiny-rwkv.json").read_text()),
            hidden_size=2560, intermediate_size=8960, head_size=64,
            num_hidden_layers=16, vocab_size=65536)
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


# -- trace reduction -----------------------------------------------------------

def _events():
    """Two devices, two harness spans; device 0 idles 1-2 and 3-4."""
    k1 = "%fused_gemm.7 = bf16[8,2560]{1,0} custom-call(s16[8,8960]{1,0} %a)"
    k2 = "%fused_gemm.9 = bf16[8,2560]{1,0} custom-call(s16[8,8960]{1,0} %b)"
    dev0 = [["%fusion.1 = s16[4]{0} fusion()", 0.0, 1.0], [k1, 2.0, 2.5],
            [k2, 2.4, 3.0], ["%copy.3 = f32[2]{0} copy()", 4.0, 5.0]]
    dev1 = [["%fusion.1 = s16[4]{0} fusion()", 0.0, 4.0]]
    host = [["bench.step", 0.0, 5.0], ["bench.record", 1.5, 2.0],
            ["bench.generator", 3.5, 4.0]]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, "host": host}


def test_busy_is_the_union_of_device_intervals_averaged_over_devices():
    ev = _events()
    win = devtrace.window(ev)
    assert win == (0.0, 5.0)
    assert devtrace.union([(2.0, 2.5), (2.4, 3.0), (0, 1)]) == [(0, 1), (2.0, 3.0)]
    # device 0 busy 3 s (overlapping kernels count once), device 1 busy 4 s
    assert devtrace.busy_seconds(ev, win) == pytest.approx(3.5)
    assert devtrace.idle_gaps(ev, win) == [(1.0, 2.0), (3.0, 4.0)]


def test_kernel_time_matches_the_instruction_and_clips_to_the_window():
    ev = _events()
    assert devtrace.kernel_seconds(ev, "%fused_gemm.", (0.0, 5.0)) == pytest.approx(1.1 / 2)
    assert devtrace.kernel_seconds(ev, "%fused_gemm.", (2.45, 5.0)) == pytest.approx(
        (0.05 + 0.55) / 2)
    assert devtrace.kernel_seconds(ev, "%fused_gemm_grouped.", (0.0, 5.0)) == 0
    assert devtrace.top_ops(ev, (0.0, 5.0))[0] == ["fused_gemm bf16[8,2560]", pytest.approx(1.1)]


def test_idle_gaps_go_to_the_innermost_host_span():
    ev = _events()
    got = dict(devtrace.attribute(ev, devtrace.idle_gaps(ev, devtrace.window(ev))))
    assert got == {"bench.step": pytest.approx(1.0),
                   "bench.record": pytest.approx(0.5),
                   "bench.generator": pytest.approx(0.5)}


# -- operations and bytes ----------------------------------------------------------

def test_gemm_ops_bytes_and_least_time_by_hand():
    assert ops.gemm_ops(2, 3, 4) == 48
    # (2*3 + 3*4) operands at 12 bits = 27 bytes, 2*4 outputs at 4 bytes = 32
    assert ops.gemm_bytes(2, 3, 4, 12) == 59
    peaks = {"int8_ops": 100.0, "hbm_bytes_per_s": 10.0}
    assert ops.least_seconds(2, 3, 4, 12, peaks) == pytest.approx(5.9)      # bytes bound
    # 2e5 operations at 100/s against 50100 bytes at 1000/s: bound by operations
    assert ops.least_seconds(1000, 10, 10, 8, dict(peaks, hbm_bytes_per_s=1000.0)) \
        == pytest.approx(2000.0)


def test_layer_gemms_at_published_widths():
    # stablelm: q 32*160, kv 8*160, SwiGLU 13824
    assert ops.layer_gemms(STABLELM) == [(5120, 5120), (5120, 1280), (5120, 1280),
                                         (5120, 5120), (5120, 13824), (5120, 13824),
                                         (13824, 5120)]
    per_layer = sum(k * n for k, n in ops.layer_gemms(STABLELM))
    assert per_layer == 277_872_640                    # 277.9M parameters a layer
    assert ops.layer_gemms(RWKV) == [(2560, 2560)] * 5 + [(2560, 8960), (8960, 2560)]
    calls = ops.calls(STABLELM, 32, 32)
    assert len(calls) == 7 * 5 + 1 and calls[-1] == (32, 5120, 100352)


def test_model_ops_of_a_prompt_and_a_decoded_token():
    cfg = dict(STABLELM, num_hidden_layers=1)
    w = 2.0 * 277_872_640
    head = 2.0 * 5120 * 100352
    attn_one = 4.0 * 5120                         # one query against one key
    assert ops.decode_ops(cfg, 9) == pytest.approx(w + head + attn_one * 10)
    assert ops.prefill_ops(cfg, 3) == pytest.approx(3 * w + head + attn_one * 6)
    r = dict(RWKV, num_hidden_layers=1)
    lin = 2.0 * (5 * 2560 * 2560 + 2 * 2560 * 8960)
    extra = 2.0 * 2 * 2560 * 64 + 4.0 * 2560 * 64
    assert ops.decode_ops(r, 100) == pytest.approx(lin + extra + 2.0 * 2560 * 65536)


# -- traffic -----------------------------------------------------------------------

@pytest.mark.parametrize("name", ["docs", "chat"])
def test_traffic_same_seed_same_schedule_and_sizes_do_not_move(name):
    mix = traffic.load(BENCH / "traffic" / f"{name}.json")
    a = traffic.generate(mix, 2 ** 31 + 12345, 40, 1000)
    b = traffic.generate(mix, 2 ** 31 + 12345, 40, 1000)
    c = traffic.generate(mix, 7, 40, 1000)
    assert a == b
    assert [x.prompt for x in a] != [x.prompt for x in c]
    # every seed gets the same set of lengths and gaps; a fixed order
    # replays one schedule, a shuffled one reorders it
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    schedule = [[(len(x.prompt), x.max_new, x.due_s) for x in items] for items in (a, c)]
    assert (schedule[0] == schedule[1]) == (mix.get("order", "shuffled") == "fixed")
    if mix["loop"] == "open":
        gap_a = np.sort(np.diff([x.due_s for x in a]))
        assert a[0].due_s == 0.0 and np.all(gap_a >= 0)
        assert len(a) == int(np.ceil(mix["arrivals"]["rate"] * 40))


@pytest.mark.parametrize("name", ["docs", "chat"])
def test_traffic_lengths_hold_their_clips_and_medians(name):
    mix = traffic.load(BENCH / "traffic" / f"{name}.json")
    for key in ("prompt", "output"):
        d = mix[key]
        n = traffic.lengths(d, 401)
        assert n.min() >= d["min"] and n.max() <= d["max"]
        median = d.get("median", (d["min"] + d["max"]) / 2)
        assert abs(np.median(n) - median) <= 1
    items = traffic.generate(mix, 3, 40, 1000)
    assert all(len(x.prompt) + x.max_new <= mix["max_seq"] for x in items)
    ladder = [8 * 2 ** i for i in range(12) if 8 * 2 ** i <= mix["max_seq"]]
    widths = traffic.prompt_buckets(mix, ladder)
    assert {traffic.bucket(len(x.prompt), ladder) for x in items} <= set(widths)


def test_gamma_gaps_keep_their_mean_and_burstiness():
    g = traffic.gaps({"process": "gamma", "shape": 0.25, "rate": 5.0}, 4000)
    assert g.mean() == pytest.approx(0.2)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.15)


# -- the comparison ----------------------------------------------------------------

def test_sample_takes_the_longest_then_draws_from_the_seed():
    pairs = [([1] * n, [2] * m) for n, m in [(5, 3), (50, 4), (7, 100), (6, 2)]]
    idx = check.sample(pairs, 11, tokens=105)
    assert idx[0] == 2 and len(idx) >= 2
    assert check.sample(pairs, 11, tokens=105) == idx
    assert check.sample([], 1) == []


def test_gaps_of_served_tokens():
    ref = np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 2.9]])
    got = check.gap_numbers([ref], [[1, 2]])
    assert got == {"served_gap": pytest.approx(0.1), "mean_gap": pytest.approx(0.05)}
    assert check.gap_numbers([ref], [[2, 0]])["served_gap"] == pytest.approx(1.0)
    seq, pos = check.teacher_forced([5, 6, 7], [8, 9])
    assert seq == [5, 6, 7, 8] and pos == [2, 3]
    ok, rows = check.verdict({"a": 0.5, "b": float("nan")}, {"a": 1.0, "b": 1.0})
    assert not ok and rows[0] == {"name": "a", "value": 0.5, "limit": 1.0}


# -- whole runs on the CPU -----------------------------------------------------------

def _run(cell, seed=2 ** 31 + 5, seconds=2.0, trace_on=0, hook=None, quant=None):
    import jax

    import run

    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace_on)
    return run.run(args, devices=jax.devices(), root=DATA, engine_hook=hook,
                   quant=quant)


def test_last_line_schema_of_a_run():
    res = _run("tiny-attn.chat")
    assert list(res)[:4] == ["correct", "attempted", "failed", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    names = [r["name"] for r in res["checks"]]
    assert names[0] == "served_gap" and "compiles_in_window" in names
    json.dumps(res)


def test_closed_loop_reports_tokens_per_second():
    res = _run("tiny-attn.docs")
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["correct"] is True


def test_no_tpu_means_no_result_and_a_nonzero_exit(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload",
           "stablelm-12b.w12.docs", "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
    # nor does it run from the benchmark's files alone
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py"] + cmd[2:], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_at_8_bits_fails_where_the_program_passes(seed):
    """The control, the program's own 8-bit path in place of its 12 bits,
    is not correct; the program at 12 bits is, on the same seed."""
    program = _run("tiny-attn.chat", seed=seed, seconds=4.0)
    control = _run("tiny-attn.chat", seed=seed, seconds=4.0, quant="w8")
    assert program["correct"] is True
    assert control["correct"] is False


def _break_sampler(engine):
    """A token altered where it is produced: every sampled token plus one."""
    ex = engine.executor
    orig = ex.sample

    def sample(*a):
        return orig(*a) + 1
    ex.sample = sample


def _break_state(engine):
    """A decode step that returns the cache state unchanged."""
    ex = engine.executor
    orig = ex.decode

    def decode(lanes, toks, pos):
        import jax

        kept = jax.tree.map(lambda a: a.copy(), engine.pool.pools)
        out = orig(lanes, toks, pos)
        engine.pool.pools = kept
        return out
    ex.decode = decode


def _break_half_batch(engine):
    """Half of the decode batch left out: the upper lanes get the lower
    lanes' logits."""
    ex = engine.executor
    orig = ex.decode

    def decode(lanes, toks, pos):
        out = orig(lanes, toks, pos)
        h = out.shape[0] // 2
        if h == 0:
            return out
        return out.at[out.shape[0] - h:].set(out[:h])
    ex.decode = decode


@pytest.mark.parametrize("cell", ["tiny-attn.chat", "tiny-rwkv.chat"])
@pytest.mark.parametrize("fault", [_break_sampler, _break_state, _break_half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(cell, fault):
    res = _run(cell, hook=fault)
    assert res["correct"] is False
    assert res["numbers"]["served_gap"] > res["checks"][0]["limit"] * 10
