"""The benchmark's rwkv6-3b cell: its configuration at the published widths,
its traffic, and the reader of ``wkv_us_per_token.chat_burst``.

The reader's events are instruction texts as a v5e compile of the 16-layer
decode-32 and prefill-512 serve programs prints them (shortened after the
operands' first name).
"""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402

CELL = "rwkv6-3b.w12.chat_burst"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


WKV = _reader("wkv_us_per_token.chat_burst")

# matched: the prefill's time-step loops and their step, the decode's state
# update, and the copies of state rows between the pool and the layers
SCAN = ("%while.58 = (s32[]{:T(128)}, f32[1,40,64,64]{3,2,1,0:T(8,128)S(1)}, "
        "f32[8,64,1,40,64]{4,3,1,2,0:T(8,128)S(1)}, f32[40,64]{1,0}) while(%tuple.127)")
STEP = ("%fusion.155 = (f32[40,64]{1,0:T(8,128)S(1)}, f32[1,40,64,64]{3,2,1,0}) "
        "fusion(%get-tuple-element.1033), kind=kLoop")
UPDATE = ("%bitcast_dynamic-update-slice_fusion.7 = f32[16,32,40,64,64]{4,3,2,0,1:T(8,128)} "
          "fusion(%get-tuple-element.506), kind=kLoop")
GATHER = ("%constant_dynamic-slice_fusion.4 = f32[16,1,40,64,64]{4,3,2,1,0:T(8,128)} "
          "fusion(%pools__pos0____wkv__.1, %bitcast.14), kind=kLoop")
KERNEL = "%wkv_gemm.3 = f32[32,40,64]{2,1,0} custom-call(f32[32,40,64]{2,1,0} %a)"
# not matched: the layer loop (it carries activations), the pool's row loop
# (it carries the row indices), a GEMM, the per-head streams alone
LAYERS = ("%while.2 = (s32[]{:T(128)}, bf16[32,1,2560]{2,0,1}, f32[16,32,40,64,64]{4,3,2,0,1}) "
          "while(%tuple.94)")
ROWS = ("%while.3 = (s32[]{:T(128)}, f32[16,33,40,64,64]{4,3,2,1,0}, s32[32,1]{0,1}, "
        "f32[32,16,40,64,64]{4,3,2,1,0}, s32[]{:T(128)}) while(%tuple.91)")
GEMM = "%fused_gemm.22 = bf16[32,65536]{1,0} custom-call(s16[32,2560]{1,0} %a)"
STREAM = "%fusion.9 = f32[512,1,40,64]{3,2,1,0} fusion(%p.1), kind=kLoop"


@pytest.mark.parametrize("name,hit", [
    (SCAN, True), (STEP, True), (UPDATE, True), (GATHER, True), (KERNEL, True),
    (LAYERS, False), (ROWS, False), (GEMM, False), (STREAM, False)])
def test_recurrence_ops_are_those_that_carry_only_the_state(name, hit):
    assert WKV.is_recurrence(name, 40, 64) is hit
    # another model's state shape matches nothing but the kernel name
    assert WKV.is_recurrence(name, 32, 64) is (name is KERNEL)


def test_us_per_token_counts_nested_ops_once_over_real_token_layers():
    ops = [[LAYERS, 0.0, 10.0], [SCAN, 1.0, 3.0], [STEP, 1.5, 1.6],
           [STEP, 2.0, 2.1], [UPDATE, 5.0, 5.5], [GEMM, 6.0, 7.0],
           [UPDATE, 9.5, 11.0]]
    events = {"devices": {"/device:TPU:0": ops}, "host": []}
    flights = [SimpleNamespace(prompt=[1] * 100, stamps=[2.0, 3.0, 4.0]),
               SimpleNamespace(prompt=[1] * 7, stamps=[-1.0, 0.5, 12.0])]
    ctx = {"cfg": json.loads((BENCH / "configs" / "rwkv6-3b.w12.json").read_text()),
           "prof": {"start": 0.0, "stop": 10.0}, "events": events,
           "traced": (0.0, 10.0), "window": SimpleNamespace(flights=flights)}
    # 2 s of scan (its steps inside it), 0.5 s of update, 0.5 s clipped at
    # the slice's end; tokens: 100 prompt + 2 decoded, and 1 decoded
    seconds = 2.0 + 0.5 + 0.5
    assert WKV.read(ctx) == pytest.approx(1e6 * seconds / (103 * 16))
    ctx["events"] = {"devices": {"/device:TPU:0": [[GEMM, 0.0, 1.0]]}, "host": []}
    assert WKV.read(ctx) is None


def test_configuration_holds_the_published_widths():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    conf = next(c for c in spec["configs"] if c["name"] == "rwkv6-3b.w12")
    cfg = json.loads((BENCH.parent / conf["file"]).read_text())
    assert (cfg["hidden_size"], cfg["head_size"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["time_decay_lora_dim"]) == (2560, 64, 8960, 65536, 64)
    assert cfg["hidden_size"] // cfg["head_size"] == cfg["published"]["num_attention_heads"]
    assert conf["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] < cfg["published"]["num_hidden_layers"]
    assert sum("departure" in a for a in cfg["assumed"]) == 5
    import run as bench_run
    pcfg = bench_run.program_config(cfg)
    assert (pcfg.d_model, pcfg.rwkv_head_dim, pcfg.n_heads, pcfg.d_ff,
            pcfg.n_periods) == (2560, 64, 40, 8960, cfg["num_hidden_layers"])


def test_bursty_traffic_keeps_its_shape():
    mix = traffic.load(BENCH / "traffic" / "chat_burst.json")
    assert mix["order"] == "fixed" and mix["slots"] == 32 and mix["max_seq"] == 1024
    assert mix["arrivals"]["shape"] == 0.25
    assert mix["arrivals"]["rate"] == round(0.8 * mix["knee_req_s"], 1)
    items = traffic.generate(mix, 2 ** 31 + 77, 50, 65536)
    gaps = np.diff([x.due_s for x in items])
    assert np.std(gaps) / np.mean(gaps) > 1.5           # bursts: CV about 2
    plen = sorted(len(x.prompt) for x in items)
    assert 32 <= plen[0] and plen[-1] <= 512 and 100 <= np.median(plen) <= 160
    assert all(16 <= x.max_new <= 256 for x in items)
    assert all(len(x.prompt) + x.max_new <= mix["max_seq"] for x in items)
