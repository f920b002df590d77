"""The serving engine stores its weights prequantized.

``Engine`` rewrites the params it is given through
:func:`repro.quant.prequant.prequantize` when quantization is enabled, so
no GEMM call re-quantizes a weight.  The same quantizer on the same f32
weights along the same axis gives the same integers and scales: the
engine's logits and greedy tokens equal, bit for bit, those of lm-level
generation that quantizes the same f32 weights at every call.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.quant.prequant import prequantize, site_name
from repro.quant.quantize import storage_dtype_for
from repro.serve.engine import Engine, Request

MAX_SEQ = 16
PROMPT = [3, 17, 5, 250, 42]
MAX_NEW = 4

# attention, recurrent state, grouped MoE
ARCHS = ["stablelm-12b", "rwkv6-3b", "granite-moe-3b-a800m"]


def _cfg(arch, backend, policy):
    cfg = get_config(arch, smoke=True, quant=policy)
    return cfg.with_quant(dataclasses.replace(cfg.quant, backend=backend))


def _runtime_generate(cfg, params):
    """Greedy lm-level generation on f32 params, at the engine's shapes:
    the prompt right-padded to its bucket, one lane, every weight
    quantized inside each call."""
    width = 8
    toks = np.zeros((1, width), np.int32)
    toks[0, :len(PROMPT)] = PROMPT
    last = jnp.array([len(PROMPT) - 1], jnp.int32)
    mask = jnp.arange(width)[None, :] <= last[:, None]
    prefill = jax.jit(lambda p, t, c: lm.prefill(
        p, cfg, t, c, pad_mask=mask, last_idx=last, start=jnp.int32(0)))
    decode = jax.jit(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))
    logits, cache, _ = prefill(params, jnp.asarray(toks),
                               lm.init_cache(cfg, 1, MAX_SEQ))
    out, seen = [], [logits]
    for step in range(MAX_NEW):
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
        if step + 1 == MAX_NEW:
            break
        logits, cache = decode(params, jnp.array([tok], jnp.int32), cache,
                               jnp.array([len(PROMPT) + step], jnp.int32))
        seen.append(logits)
    return out, seen


def _n_records(tree) -> int:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return sum(getattr(path[-1], "key", None) == "q" for path, _ in paths)


def _engine_generate(cfg, params):
    eng = Engine(cfg, params, max_seq=MAX_SEQ, batch_size=1, rng_seed=0)
    seen = []
    ex = eng.executor
    prefill, decode = ex.prefill, ex.decode
    ex.prefill = lambda *a: seen.append(prefill(*a)) or seen[-1]
    ex.decode = lambda *a: seen.append(decode(*a)) or seen[-1]
    req = Request(prompt=list(PROMPT), max_new_tokens=MAX_NEW)
    eng.generate([req])
    return eng, req.generated, seen


@pytest.mark.parametrize("policy", ["w12", "mixed"])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("arch", ARCHS)
def test_engine_prequant_matches_runtime_quantized(arch, backend, policy):
    cfg = _cfg(arch, backend, policy)
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    eng, toks, seen = _engine_generate(cfg, params)
    ref_toks, ref_seen = _runtime_generate(cfg, params)
    # the engine serves records; the caller's f32 tree is left as it was
    assert _n_records(eng.params) > 0 and _n_records(params) == 0
    assert toks == ref_toks
    assert len(seen) == len(ref_seen)
    for got, want in zip(seen, ref_seen):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("arch", ARCHS)
def test_warm_engine_quantizes_no_weight_at_run_time(arch):
    """After ``warm()`` every quantized GEMM of every decode and prefill
    program read a stored record.  A tied head quantizes the embedding at
    run time (it is also the lookup table), so the head is untied here."""
    cfg = _cfg(arch, "pallas", "w12")
    cfg = dataclasses.replace(cfg, tie_embeddings=False)
    params = lm.init_params(jax.random.PRNGKey(2), cfg)
    obs_metrics.enable()
    try:
        obs_metrics.reset()
        Engine(cfg, params, max_seq=MAX_SEQ, batch_size=2).warm()
        source = obs_metrics.get("repro_quant_weight_source_total")
        assert source.value("runtime") == 0
        assert source.value("prequant") > 0
    finally:
        obs_metrics.disable()
        obs_metrics.reset()


def _model_site_names(cfg, params):
    """Every name the model passes to ``bits_for`` in a prefill."""
    names = []

    class Recording(type(cfg.quant)):
        def bits_for(self, name):
            names.append(name)
            return super().bits_for(name)

    rcfg = cfg.with_quant(Recording(**dataclasses.asdict(cfg.quant)))
    jax.eval_shape(lambda p: lm.prefill(p, rcfg, jnp.zeros((1, 8), jnp.int32),
                                        lm.init_cache(rcfg, 1, MAX_SEQ)),
                   params)
    return set(names)


@pytest.mark.parametrize("arch", ["stablelm-12b", "rwkv6-3b",
                                  "granite-moe-3b-a800m", "jamba-v0.1-52b"])
def test_prequantize_uses_the_model_site_names(arch):
    """Under overrides written against the model's site names, each record
    takes the width (its largest magnitude is that width's qmax) and the
    storage dtype that ``bits_for`` gives the name the model passes at
    that site."""
    base = get_config(arch, smoke=True, quant="w12")
    quant = dataclasses.replace(base.quant, overrides=(
        ("blk*.attn.wo", 8), ("blk0.*.wi", 16), ("blk1.rwkv.wr", 8),
        ("*router", 8), ("lm_head", 14)))
    cfg = dataclasses.replace(base.with_quant(quant), tie_embeddings=False)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    recs = {}

    def collect(path, leaf):
        if isinstance(leaf, dict) and set(leaf) == {"q", "scale"}:
            recs[site_name(path)] = leaf["q"]
            return None
        return leaf

    jax.tree_util.tree_map_with_path(
        collect, prequantize(params, quant),
        is_leaf=lambda x: isinstance(x, dict) and "q" in x)
    used = _model_site_names(cfg, params)
    assert used, "the model named no quantized site"
    assert used <= set(recs), f"sites with no record: {used - set(recs)}"
    assert len({quant.bits_for(n) for n in used}) > 1, \
        "the overrides matched no site"
    for name in used:
        bits = quant.bits_for(name)
        q = recs[name]
        assert q.dtype == storage_dtype_for(bits), name
        assert int(jnp.abs(q.astype(jnp.int32)).max()) == 2 ** (bits - 1) - 1, \
            name
