"""Serving a mixed stack (Mamba-2, MLP and attention layers by a pattern)
through ``LLMEngine``: recurrent state in per-request slots beside the
paged KV pool, which holds the attention layers only.

Every comparison is against ``_reference_logits`` below: the stack's
equations in plain jnp float32 over the whole sequence, the Mamba-2
recurrence as a sequential scan over time, no kernels, cache or batching.
The model runs in float32 here too, so the program and the reference
differ only by summation order: the chunked scan regroups the recurrence's
sums over 128-token sub-chunks and the kernels their dot products. Over
these sizes that moves a logit by about 1e-6 of the row's range;
``LOGIT_TOL`` (1e-4 of the range) leaves a hundredfold margin, while a
wrong state slot, a state not carried between chunks, a skipped conv
window or a position off by one moves logits by a large part of the range.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer
from repro.models.common import ModelConfig
from repro.serving import EngineConfig, LLMEngine, Request, SamplingParams
from repro.serving.request import State

# every layer kind, two attention layers (the pool holds those two)
CFG = ModelConfig(
    name="tiny-mixed", family="mixed", layer_pattern="M*-M-*",
    num_layers=6, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=96, vocab_size=128, ssm_state=16, ssm_head_dim=16, ssm_heads=8,
    ssm_groups=2, ssm_conv=4, rope_theta=0.0, norm_eps=1e-5,
    dtype=jnp.float32)
LOGIT_TOL = 1e-4


def _params(seed: int = 0):
    """Random weights with every parameter away from its initial value:
    decay A in [-16, -1], softplus(dt_bias) in [0.01, 0.5] (steps large
    enough that the state changes over a short prompt), norms, conv bias
    and D drawn."""
    params = transformer.init_params(jax.random.PRNGKey(seed), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def draw(a, scale):
        return a + scale * jax.random.normal(next(keys), a.shape, a.dtype)

    m = params["layers"]["mamba"]["mamba"]
    H = CFG.ssm_heads
    n = m["a_log"].shape[0]
    m["a_log"] = jnp.log(jax.random.uniform(next(keys), (n, H), minval=1.0,
                                            maxval=16.0))
    dt = jnp.exp(jax.random.uniform(next(keys), (n, H),
                                    minval=np.log(0.01), maxval=np.log(0.5)))
    m["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    m["d_skip"] = draw(m["d_skip"], 0.1)
    m["conv_b"] = draw(m["conv_b"], 0.1)
    m["out_norm"] = draw(m["out_norm"], 0.1)
    for stack in params["layers"].values():
        stack["norm"] = draw(stack["norm"], 0.1)
    params["final_norm"] = draw(params["final_norm"], 0.1)
    return params


PARAMS = _params()


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _rms(x, gain, eps=CFG.norm_eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        (1.0 + gain)


def _mamba_ref(p, x):
    S, H, P, N = x.shape[0], CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state
    G, K = CFG.ssm_groups, CFG.ssm_conv
    di = H * P
    proj = x @ p["w_in"]
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * G * N], \
        proj[:, 2 * di + 2 * G * N:]
    past = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = jax.nn.silu(sum(past[i:i + S] * p["conv_w"][i] for i in range(K))
                       + p["conv_b"])
    xs = conv[:, :di].reshape(S, H, P)
    Bm = conv[:, di:di + G * N].reshape(S, G, N)
    Cm = conv[:, di + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    g = np.arange(H) // (H // G)
    s, ys = jnp.zeros((H, P, N)), []
    for t in range(S):
        s = (jnp.exp(dt[t] * A)[:, None, None] * s
             + (dt[t][:, None] * xs[t])[:, :, None] * Bm[t][g][:, None, :])
        ys.append(jnp.einsum("hpn,hn->hp", s, Cm[t][g],
                             precision="highest"))
    y = (jnp.stack(ys) + p["d_skip"][:, None] * xs).reshape(S, di)
    y = y * jax.nn.silu(z)
    y = _rms(y.reshape(S, G, di // G), p["out_norm"].reshape(G, di // G))
    return y.reshape(S, di) @ p["w_out"]


def _attention_ref(p, x):
    S = x.shape[0]
    q = jnp.einsum("sd,dhk->shk", x, p["wq"])
    k = jnp.einsum("sd,dhk->shk", x, p["wk"])
    v = jnp.einsum("sd,dhk->shk", x, p["wv"])
    rep = CFG.num_heads // CFG.num_kv_heads
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    s = jnp.einsum("qhk,thk->hqt", q, k) / np.sqrt(CFG.head_dim)
    s = jnp.where(np.tril(np.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(s, -1), v)
    return jnp.einsum("shk,hkd->sd", o, p["wo"])


def _reference_logits(params, tokens):
    """(S, V) float32 logits at every position of one sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)]
        seen = {"M": 0, "-": 0, "*": 0}
        for kind in CFG.layer_pattern:
            name = transformer.STACKS[kind]
            p = jax.tree.map(lambda a: a[seen[kind]],
                             params["layers"][name])
            seen[kind] += 1
            h = _rms(x, p["norm"])
            if kind == "M":
                x = x + _mamba_ref(p["mamba"], h)
            elif kind == "-":
                x = x + jnp.square(jax.nn.relu(h @ p["w_up"])) @ p["w_down"]
            else:
                x = x + _attention_ref(p["attn"], h)
        return np.asarray(_rms(x, params["final_norm"]) @ params["lm_head"])


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _engine(**kw):
    conf = dict(num_blocks=64, block_size=16, max_batch=3,
                decode_backend="pallas")
    conf.update(kw)
    return LLMEngine(CFG, PARAMS, EngineConfig(**conf))


def _record(eng):
    """Keep every logit row the engine samples from, by (rid, token)."""
    got = {}
    sample = eng._sample

    def keep(reqs, logits):
        for r, row in zip(reqs, np.asarray(logits)):
            got.setdefault(r.rid, {})[len(r.output)] = row
        return sample(reqs, logits)

    eng._sample = keep
    return got


def _requests(lengths, new_tokens, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=[int(t) for t in rng.integers(0, 128, n)],
                    params=SamplingParams(max_new_tokens=new_tokens,
                                          temperature=0.0))
            for n in lengths]


def _assert_matches_reference(reqs, got):
    for r in reqs:
        seq = r.prompt + r.output[:-1]
        want = _reference_logits(PARAMS, seq)[len(r.prompt) - 1:]
        have = np.stack([got[r.rid][k] for k in range(len(r.output))])
        span = want.max(axis=1) - want.min(axis=1)
        err = np.abs(have - want).max(axis=1)
        assert (err <= LOGIT_TOL * span).all(), (r.rid, err / span)
        # greedy: every served token is the reference's best, or a tie
        best = want.max(axis=1)
        at = want[np.arange(len(r.output)), r.output]
        assert (best - at <= 2 * LOGIT_TOL * span).all()


# ---------------------------------------------------------------------------
# the kernels against jnp
# ---------------------------------------------------------------------------
def _scan_ref(xdt, a, B, C, s0):
    Bb, S, H, P = xdt.shape
    g = np.arange(H) // (H // B.shape[2])

    def step(s, inp):
        x, aa, b, c = inp
        s = s * jnp.exp(aa)[..., None, None] + x[..., None] * \
            b[:, g][:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c[:, g],
                             precision="highest")

    s, ys = jax.lax.scan(step, s0, tuple(v.swapaxes(0, 1)
                                         for v in (xdt, a, B, C)))
    return ys.swapaxes(0, 1), s


@pytest.mark.parametrize("S,H,P,N,G", [
    (45, 8, 16, 16, 2),        # one sub-chunk, padded
    (300, 32, 64, 16, 2),      # three sub-chunks, two head tiles a group
])
def test_grouped_chunk_scan_from_a_state_matches_jnp(S, H, P, N, G):
    """float32 in, float32 out; the chunked form regroups the sums, so
    y and the final state agree to 1e-5 of their largest magnitude."""
    from repro.kernels import ssm_scan as K

    ks = jax.random.split(jax.random.PRNGKey(S), 5)
    xdt = jax.random.normal(ks[0], (1, S, H, P)) * 0.5
    a = -jax.nn.softplus(jax.random.normal(ks[1], (1, S, H)))
    B = jax.random.normal(ks[2], (1, S, G, N)) * 0.5
    C = jax.random.normal(ks[3], (1, S, G, N)) * 0.5
    s0 = jax.random.normal(ks[4], (1, H, P, N))
    r = K.heads_per_row(H // G, P)
    y, fin = K.ssm_chunk_scan(xdt, a, B, C, K.pair_state(s0, r),
                              interpret=True)
    want_y, want_s = _scan_ref(xdt, a, B, C, s0)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(K.unpair_state(fin, r)),
                               np.asarray(want_s),
                               atol=1e-5 * float(jnp.abs(want_s).max()))


def test_decode_update_writes_its_rows_slots_in_place_and_no_other():
    """One float32 step: exact but for the order of the C contraction."""
    from repro.kernels import ssm_scan as K
    from repro.kernels.ssm_decode_update import ssm_decode_update

    L, n_slots, B, H, P, N, G = 3, 6, 3, 32, 64, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    st = jax.random.normal(ks[0], (L, n_slots, H, P, N))
    slots = jnp.asarray([5, 0, 3], jnp.int32)
    x = jax.random.normal(ks[1], (B, H, P))
    d = jax.nn.sigmoid(jax.random.normal(ks[2], (B, H)))
    Bm = jax.random.normal(ks[3], (B, G, N))
    Cm = jax.random.normal(ks[4], (B, G, N))
    r = K.heads_per_row(H // G, P)
    y, new = ssm_decode_update(K.pair_state(st, r), slots, x, d, Bm, Cm,
                               layer=1, interpret=True)
    g = np.arange(H) // (H // G)
    s = st[1, slots] * d[..., None, None] + \
        x[..., None] * Bm[:, g][:, :, None, :]
    want_y = jnp.einsum("bhpn,bhn->bhp", s, Cm[:, g], precision="highest")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               atol=1e-5 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(np.asarray(K.unpair_state(new, r)),
                               np.asarray(st.at[1, slots].set(s)),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# the engine against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("placement,chunk,backend", [
    ("homogeneous", None, "pallas"),       # one-shot prefill
    ("homogeneous", 48, "jnp"),            # chunks of 48: not on the
    ("attention_pool", 48, "pallas"),      # scan's 128-token sub-chunks
    ("attention_pool", None, "jnp"),
])
def test_prefill_and_decode_through_pool_and_slots_match_reference(
        placement, chunk, backend):
    """Three prompts of unequal length (one of a single token-block, one
    over two chunks) decode in one batch through the attention pool and
    the state slots."""
    eng = _engine(placement=placement, partition="head",
                  attention_workers=2, prefill_chunk_tokens=chunk,
                  decode_backend=backend)
    got = _record(eng)
    reqs = _requests((45, 100, 7), 6)
    eng.submit(reqs)
    eng.run()
    assert all(len(r.output) == 6 for r in reqs)
    assert max(eng.stats.batch_sizes) == 3
    if chunk:
        assert eng.stats.prefill_chunks_run == 1 + 3 + 1
    _assert_matches_reference(reqs, got)
    # the pool holds the two attention layers; the slots every Mamba layer
    assert eng.kv.k_pool.shape[0] == 2
    assert eng.kv.state["ssm"].shape[:2] == (2, 3)
    assert eng.stats.state_slots_peak == 3
    assert eng.stats.state_bytes_resident == sum(
        a.nbytes for a in eng.kv.state.values())


def test_preemption_drops_the_state_and_recompute_restores_it():
    """A pool too small for every request's growth: the preempt policy
    evicts, the victim's slot is freed, and re-admission recomputes its
    state from prompt and output; its logits still match the reference."""
    eng = _engine(scheduler="preempt", num_blocks=10, decode_headroom=0,
                  prefill_chunk_tokens=32)
    got = _record(eng)
    reqs = _requests((40, 40, 40), 20, seed=3)
    eng.submit(reqs)
    eng.run()
    assert eng.stats.preemptions >= 1
    assert any(e.kind == "readmit" for e in eng.event_log)
    assert all(len(r.output) == 20 for r in reqs)
    _assert_matches_reference(reqs, got)
    assert not eng.kv.slot_of and len(eng.kv._free_slots) == 3


def test_a_slot_is_taken_at_admission_and_freed_exactly_at_retirement():
    eng = _engine(max_batch=2, prefill_chunk_tokens=32)
    reqs = _requests((20, 50, 30), 4, seed=5)
    reqs[1].params = SamplingParams(max_new_tokens=9, temperature=0.0)
    eng.submit(reqs)
    while eng.has_work():
        eng.step()
        running = {r.rid for r in eng.sched.running}
        # a slot per running request, none shared, none held by another
        assert set(eng.kv.slot_of) == running
        assert len(set(eng.kv.slot_of.values())) == len(running)
        assert len(eng.kv._free_slots) == 2 - len(running)
        for r in reqs:
            if r.state == State.FINISHED:
                assert r.rid not in eng.kv.slot_of


def test_recurrent_stacks_refuse_prefix_sharing_handoff_and_faults():
    from repro.serving.cluster import DecodeEngine, PrefillEngine
    from repro.serving.faults import FaultInjector, FaultScenario

    with pytest.raises(ValueError, match="prefix sharing"):
        _engine(prefix_sharing=True)
    for role in (PrefillEngine, DecodeEngine):
        with pytest.raises(ValueError, match="handoff"):
            role(CFG, PARAMS, EngineConfig(num_blocks=64))
    with pytest.raises(ValueError, match="fault injection"):
        LLMEngine(CFG, PARAMS, EngineConfig(num_blocks=64),
                  fault_injector=FaultInjector(FaultScenario([])))
