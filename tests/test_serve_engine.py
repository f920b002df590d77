"""Continuous-batching serve engine tests.

Covers the PR-2 acceptance bar: token-identity between continuous batching
and sequential single-request generation on ragged prompts (mixed lengths,
mixed budgets, EOS mid-stream, mixed temperature), the ragged-prefill
regression (padded-group prefill == per-request unpadded prefill), slot
scheduling (1-token request does 1 token of work, slot reuse), and the
throughput accounting fix (tokens/s counts generated tokens, not steps).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import Engine, Request, ServeStats


def _tiny_cfg():
    return get_config("llama3.2-1b", smoke=True).scaled_down(
        d_model=64, d_ff=128, vocab_size=256, n_heads=4, n_kv_heads=2,
        head_dim=16)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_cfg()
    params = lm.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _mk_requests(cfg, spec):
    rng = np.random.default_rng(0)
    return [Request(prompt=list(rng.integers(1, cfg.vocab_size, size=n)),
                    max_new_tokens=m, temperature=t, stop_tokens=stop)
            for n, m, t, stop in spec]


# ---------------------------------------------------------------------------
# Golden test: continuous batching == sequential generation, token for token.
# ---------------------------------------------------------------------------


def test_continuous_matches_sequential_ragged(tiny):
    cfg, params = tiny
    spec = [(3, 6, 0.0, ()), (9, 1, 0.0, ()), (5, 8, 0.7, ()),
            (12, 4, 0.0, ()), (2, 5, 0.9, ())]

    def run(bs, spec):
        eng = Engine(cfg, params, max_seq=48, batch_size=bs, rng_seed=3)
        reqs = _mk_requests(cfg, spec)
        eng.generate(reqs)
        return [r.generated for r in reqs]

    batched = run(3, spec)
    sequential = run(1, spec)
    assert batched == sequential
    assert [len(g) for g in batched] == [6, 1, 8, 4, 5]

    # EOS mid-stream: pick a token the longest request actually emits
    # mid-generation and rerun both ways with it as a stop token.
    eos = batched[2][2]
    spec_eos = [(n, m, t, (eos,)) for n, m, t, _ in spec]
    b2 = run(3, spec_eos)
    s2 = run(1, spec_eos)
    assert b2 == s2
    assert b2[2][-1] == eos and len(b2[2]) == 3      # truncated at the EOS
    for g, (_, m, _, _) in zip(b2, spec_eos):
        assert len(g) <= m


def test_temperature_rows_deterministic_and_mixed(tiny):
    cfg, params = tiny
    spec = [(4, 5, 0.0, ()), (4, 5, 1.0, ())]

    def run():
        eng = Engine(cfg, params, max_seq=32, batch_size=2, rng_seed=11)
        reqs = _mk_requests(cfg, spec)
        eng.generate(reqs)
        return [r.generated for r in reqs]

    a, b = run(), run()
    assert a == b                       # per-(request, step) keys: replayable
    assert all(len(g) == 5 for g in a)


# ---------------------------------------------------------------------------
# Ragged-prefill regression: padded group == per-request unpadded prefill.
# ---------------------------------------------------------------------------


def _ragged_prefill_check(arch, pad):
    cfg = get_config(arch, smoke=True)
    params = lm.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(1)
    lens = [3, 9, 5, 12]
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    S, smax, b = 16, 32, len(lens)
    toks = np.zeros((b, S), np.int32)
    pos = np.zeros((b, S), np.int32)
    mask = np.zeros((b, S), bool)
    last = np.zeros((b,), np.int32)
    for i, p in enumerate(prompts):
        n = len(p)
        if pad == "right":
            toks[i, :n] = p
            pos[i] = np.arange(S)
            mask[i, :n] = True
            last[i] = n - 1
        else:
            toks[i, S - n:] = p
            pos[i, S - n:] = np.arange(n)
            mask[i, S - n:] = True
            last[i] = S - 1
    cache = lm.init_cache(cfg, b, smax)
    logits, _, _ = lm.prefill(
        params, cfg, jnp.asarray(toks), cache, positions=jnp.asarray(pos),
        pad_mask=jnp.asarray(mask), last_idx=jnp.asarray(last))
    for i, p in enumerate(prompts):
        c1 = lm.init_cache(cfg, 1, smax)
        ref, _, _ = lm.prefill(params, cfg, jnp.asarray(p[None]), c1)
        np.testing.assert_allclose(
            np.asarray(logits[i], np.float32),
            np.asarray(ref[0], np.float32), atol=1e-4, rtol=1e-4,
            err_msg=f"{arch} {pad}-pad row {i} (len {len(p)})")


@pytest.mark.parametrize("pad", ["right", "left"])
def test_ragged_prefill_matches_unpadded_attn(pad):
    _ragged_prefill_check("llama3.2-1b", pad)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["jamba-v0.1-52b", "rwkv6-3b"])
@pytest.mark.parametrize("pad", ["right", "left"])
def test_ragged_prefill_matches_unpadded_recurrent(arch, pad):
    """Recurrent state (mamba conv/ssm, rwkv shift/wkv) across pad tokens."""
    _ragged_prefill_check(arch, pad)


# ---------------------------------------------------------------------------
# Scheduler behaviour.
# ---------------------------------------------------------------------------


def test_one_token_request_does_one_token_of_work(tiny):
    """A 1-token request in a group with a long request must not ride the
    long request's decode loop (the old group barrier ran max(max_new) steps
    for everyone and appended past the budget)."""
    cfg, params = tiny
    eng = Engine(cfg, params, max_seq=48, batch_size=2)
    reqs = _mk_requests(cfg, [(4, 1, 0.0, ()), (4, 12, 0.0, ())])
    stats = eng.generate(reqs)
    assert [len(r.generated) for r in reqs] == [1, 12]
    # first tokens come from prefill; the batch only steps for the long one
    assert stats.decode_steps == 11
    assert stats.generated_tokens == 13

    # alone, a 1-token request takes zero decode steps
    eng1 = Engine(cfg, params, max_seq=48, batch_size=1)
    r = _mk_requests(cfg, [(4, 1, 0.0, ())])
    s1 = eng1.generate(r)
    assert len(r[0].generated) == 1 and s1.decode_steps == 0

    # streaming API: a request finishing at admission is still reported by
    # the step() that admitted it
    r2 = _mk_requests(cfg, [(4, 1, 0.0, ())])[0]
    eng1.submit(r2)
    assert eng1.step() == [r2] and len(r2.generated) == 1


def test_tokens_per_s_counts_generated_tokens(tiny):
    cfg, params = tiny
    # pure accounting: 10 tokens in 2s of model time = 5 tok/s, whatever
    # the number of batch steps
    s = ServeStats(decode_s=2.0, decode_steps=64, generated_tokens=10)
    assert s.tokens_per_s == 5.0
    eng = Engine(cfg, params, max_seq=48, batch_size=4)
    reqs = _mk_requests(cfg, [(3, 4, 0.0, ())] * 4)
    stats = eng.generate(reqs)
    assert stats.generated_tokens == sum(len(r.generated) for r in reqs) == 16
    # measured runs divide by the engine-busy wall-clock span, which covers
    # at least the model time (prefill + decode) plus host bookkeeping
    assert stats.busy_s >= stats.prefill_s + stats.decode_s
    assert stats.tokens_per_s == pytest.approx(
        stats.generated_tokens / stats.busy_s)
    # a 1-token workload produces all its tokens in prefill: decode_s is 0
    # but throughput must still be real (the old metric divided by zero)
    r1 = _mk_requests(cfg, [(3, 1, 0.0, ())] * 2)
    s1 = eng.generate(r1)
    assert s1.decode_steps == 0 and s1.generated_tokens == 2
    assert s1.tokens_per_s > 0


def test_slot_reuse_and_continuous_admission(tiny):
    """More requests than slots: freed slots are refilled mid-flight and
    every request completes; the decode jit never retraces."""
    cfg, params = tiny
    eng = Engine(cfg, params, max_seq=48, batch_size=2)
    spec = [(3, 2, 0.0, ()), (5, 6, 0.0, ()), (4, 3, 0.0, ()),
            (6, 1, 0.0, ()), (2, 4, 0.0, ())]
    reqs = _mk_requests(cfg, spec)
    stats = eng.generate(reqs)
    assert [len(r.generated) for r in reqs] == [m for _, m, _, _ in spec]
    assert len(stats.requests) == 5
    # bucketed decode on a 2-slot engine traces at most widths {1, 2}
    nt = eng.n_traces()["decode"]
    assert nt == -1 or 1 <= nt <= 2
    # continuous batching: total steps is far below the group-barrier cost
    # (ceil(5/2) groups x max_new=6 would be 18 steps)
    assert stats.decode_steps < 18


def test_eos_and_stats(tiny):
    cfg, params = tiny
    eng = Engine(cfg, params, max_seq=48, batch_size=2)
    reqs = _mk_requests(cfg, [(4, 8, 0.0, ()), (5, 8, 0.0, ())])
    eng.generate(reqs)
    eos = reqs[0].generated[1]
    reqs2 = _mk_requests(cfg, [(4, 8, 0.0, (eos,)), (5, 8, 0.0, ())])
    stats = eng.generate(reqs2)
    cut = reqs[0].generated.index(eos) + 1    # truncated at first occurrence
    assert reqs2[0].generated == reqs[0].generated[:cut]
    by_rid = {r.rid: r for r in stats.requests}
    gen_by_rid = {r.stats.rid: r.generated for r in reqs2}
    assert by_rid[reqs2[0].stats.rid].stop_reason == "stop_token"
    assert by_rid[reqs2[1].stats.rid].stop_reason == "length"
    for rs in stats.requests:
        assert rs.prompt_len in (4, 5)
        assert rs.first_token_s >= rs.arrival_s
        assert rs.latency_s >= rs.ttft_s >= 0
        assert rs.n_tokens == len(gen_by_rid[rs.rid])


def test_granite_moe_grouped_serve_smoke():
    """PR-9 acceptance: a quantized MoE model serves with its expert GEMMs
    routed through the ragged grouped fused kernel (live counts from the
    capacity dispatch), token-identical to the XLA backend — and the route
    + dispatch metrics prove the path was actually taken."""
    import dataclasses
    from repro.obs import metrics as obs_metrics

    cfg = get_config("granite-moe-3b-a800m", smoke=True)
    cfg = cfg.with_quant(dataclasses.replace(
        cfg.quant, enabled=True, default_bits=8))
    params = lm.init_params(jax.random.PRNGKey(5), cfg)
    spec = [(6, 4, 0.0, ()), (3, 3, 0.0, ())]

    def run(backend):
        eng = Engine(cfg, params, max_seq=32, batch_size=2,
                     quant_backend=backend, rng_seed=5)
        reqs = _mk_requests(cfg, spec)
        eng.generate(reqs)
        return [r.generated for r in reqs]

    obs_metrics.enable()
    try:
        obs_metrics.reset()
        xla_toks = run("xla")
        pal_toks = run("pallas")
        routes = obs_metrics.get("repro_quant_gemm_routes_total")
        assert routes.value("pallas", "pallas") > 0, \
            "no quantized GEMM actually took the pallas route"
        hist = obs_metrics.snapshot().get("repro_moe_tokens_per_expert")
        assert hist and any(v["count"] > 0 for v in hist["values"].values()), \
            "MoE dispatch histogram never observed"
    finally:
        obs_metrics.disable()
        obs_metrics.reset()
    assert xla_toks == pal_toks, "pallas MoE serve is not token-identical"


def test_submit_rejects_oversized(tiny):
    cfg, params = tiny
    eng = Engine(cfg, params, max_seq=16, batch_size=1)
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=[1] * 10, max_new_tokens=10))
    # the first token is produced at admission, so a zero budget is
    # rejected up front rather than silently over-generating
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=[1, 2], max_new_tokens=0))
    # custom buckets bound the admissible prompt length at submit time
    # (not deep inside the serve loop, where the request would be lost)
    eng2 = Engine(cfg, params, max_seq=64, batch_size=1, prompt_buckets=[8])
    with pytest.raises(ValueError):
        eng2.submit(Request(prompt=[1] * 20, max_new_tokens=8))


def test_encdec_unsupported_is_explicit():
    cfg = get_config("seamless-m4t-medium", smoke=True)
    assert cfg.is_encdec
    with pytest.raises(NotImplementedError):
        Engine(cfg, params={}, max_seq=16, batch_size=1)


def test_arrival_trace_queues_admission(tiny):
    cfg, params = tiny
    eng = Engine(cfg, params, max_seq=32, batch_size=2)
    reqs = _mk_requests(cfg, [(3, 2, 0.0, ()), (3, 2, 0.0, ()),
                              (3, 2, 0.0, ())])
    stats = eng.generate(reqs, arrival_s=[0.0, 0.05, 0.1])
    assert all(len(r.generated) == 2 for r in reqs)
    for rs in stats.requests:
        assert rs.first_token_s >= rs.arrival_s


def test_serve_quant_backend_pallas_token_identical(tiny):
    cfg, params = tiny
    spec = [(5, 4, 0.0, ()), (9, 3, 0.8, ()), (3, 4, 0.0, ())]

    def run(backend, quant):
        qcfg = cfg.with_quant(get_config("llama3.2-1b", smoke=True,
                                         quant=quant).quant)
        eng = Engine(qcfg, params, max_seq=32, batch_size=2, rng_seed=5,
                     quant_backend=backend)
        reqs = _mk_requests(qcfg, spec)
        eng.generate(reqs)
        return [r.generated for r in reqs]

    # w8: the exact-int class makes the fused epilogue bit-identical to the
    # XLA dequant, so logits — and therefore tokens, greedy AND sampled —
    # cannot differ.
    assert run("xla", "w8") == run("pallas", "w8")
    # w12: the fused kernel is in the staged-pallas fp32 class; at serve
    # scale the accumulators stay integer-exact in fp32, so tokens match
    # the XLA digit recursion too.
    assert run("xla", "w12") == run("pallas", "w12")


def test_engine_pallas_under_mesh_negotiates(tiny):
    """The old hard mesh-rejection is gone: pallas + mesh serves through
    capability negotiation.  On a 1x1 mesh no axis can tile any GEMM, so
    every quantized matmul downgrades to XLA (logged) — and the tokens must
    match the same engine without a mesh."""
    from repro.core.context import ExecContext

    cfg, params = tiny
    qcfg = cfg.with_quant(get_config("llama3.2-1b", smoke=True,
                                     quant="w8").quant)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    spec = [(5, 3, 0.0, ()), (9, 2, 0.8, ())]

    def run(mesh_arg):
        eng = Engine(qcfg, params, max_seq=32, batch_size=2, rng_seed=5,
                     context=ExecContext(backend="pallas", mesh=mesh_arg))
        assert eng.context.backend == "pallas"
        reqs = _mk_requests(qcfg, spec)
        eng.generate(reqs)
        return [r.generated for r in reqs]

    assert run(mesh) == run(None)
