"""Cross-cutting property tests: cost-model monotonicity/limits, converter
cuts on randomized graphs, checkpoint dtype preservation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.core import converter, costmodel as cm

# hypothesis-heavy sweeps: CI's blocking matrix skips them (-m "not slow");
# the non-blocking slow job still runs the file on every PR
pytestmark = pytest.mark.slow


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
@settings(deadline=None, max_examples=30)
@given(b1=st.integers(1, 512), b2=st.integers(1, 512))
def test_mtime_monotone_in_batch(b1, b2):
    cfg = registry.get_config("llama3-70b")
    hw = cm.HARDWARE["h100"]
    lo, hi = sorted((b1, b2))
    assert cm.mtime(cfg, lo, hw) <= cm.mtime(cfg, hi, hw) + 1e-12


@settings(deadline=None, max_examples=30)
@given(b=st.integers(1, 512), l=st.integers(128, 32768))
def test_atime_linear_in_batch_and_seq(b, l):
    """BGEMV: attention time scales with B·l (the paper's §2.2.2 point that
    batching does not improve attention's arithmetic intensity)."""
    cfg = registry.get_config("llama3-70b")
    hw = cm.HARDWARE["h20"]
    t1 = cm.atime(cfg, b, l, hw)
    t2 = cm.atime(cfg, 2 * b, l, hw)
    t3 = cm.atime(cfg, b, 2 * l, hw)
    assert t2 == pytest.approx(2 * t1, rel=1e-6)
    assert t3 == pytest.approx(2 * t1, rel=1e-6)


@settings(deadline=None, max_examples=30)
@given(b=st.integers(1, 300), l=st.sampled_from([1024, 4096, 8192]),
       alpha=st.floats(0.05, 0.5))
def test_min_bandwidth_decreases_with_alpha(b, l, alpha):
    cfg = registry.get_config("llama3-70b")
    h100, h20 = cm.HARDWARE["h100"], cm.HARDWARE["h20"]
    bw1 = cm.minimum_bandwidth(cfg, b, l, h100, h20, alpha=alpha)
    bw2 = cm.minimum_bandwidth(cfg, b, l, h100, h20, alpha=alpha * 2)
    assert bw2 == pytest.approx(bw1 / 2, rel=1e-6)


def test_lamina_estimate_internally_consistent():
    cfg = registry.get_config("llama3-70b")
    h100, h20 = cm.HARDWARE["h100"], cm.HARDWARE["h20"]
    est = cm.estimate_lamina(cfg, 4096, h100, h20, (2, 4))
    assert est.cost_hr == pytest.approx(2 * h100.price_hr + 4 * h20.price_hr)
    assert est.throughput_tok_s * est.tbt_s >= est.batch * 0.99  # pipelining
    assert est.tok_per_dollar == pytest.approx(
        est.throughput_tok_s * 3600 / est.cost_hr)


# ---------------------------------------------------------------------------
# converter on randomized block graphs
# ---------------------------------------------------------------------------
@settings(deadline=None, max_examples=20)
@given(layers=st.integers(1, 4), batch=st.sampled_from([1, 4]),
       seed=st.integers(0, 1000))
def test_converter_random_multilayer_exec_parity(layers, batch, seed):
    """Random per-edge weights; sliced execution must equal direct execution
    and produce exactly n_attention + 1 slices with valid topo programs."""
    rng = np.random.default_rng(seed)
    g = converter.OpGraph()
    d = 8
    g.add("x", "input", [], int(rng.integers(1, 100)))
    prev = "x"
    mats = {}
    for i in range(layers):
        p = f"l{i}_"
        for name, kind, inputs in [
                ("norm", "norm", [prev]),
                ("q", "q_proj", [p + "norm"]),
                ("k", "kv_proj", [p + "norm"]),
                ("v", "kv_proj", [p + "norm"]),
        ]:
            mats[p + name] = rng.standard_normal((d, d)).astype(np.float32)
            g.add(p + name, kind, inputs, int(rng.integers(1, 100)),
                  fn=(lambda h, W=mats[p + name]: h @ W))
        g.add(p + "attention", "attention", [p + "q", p + "k", p + "v"],
              int(rng.integers(1, 100)))
        mats[p + "o"] = rng.standard_normal((d, d)).astype(np.float32)
        g.add(p + "o", "proj", [p + "attention"], int(rng.integers(1, 100)),
              fn=(lambda a, W=mats[p + "o"]: a @ W))
        g.add(p + "res", "add", [prev, p + "o"], int(rng.integers(1, 100)),
              fn=lambda x, o: x + o)
        prev = p + "res"

    sp = converter.split_at_attention(g)
    assert len(sp.slices) == layers + 1

    def attn_fn(name, env):
        lid = name.split("_")[0]
        return env[f"{lid}_q"] + env[f"{lid}_v"]  # arbitrary deterministic

    x = rng.standard_normal((batch, d)).astype(np.float32)
    env = sp.run({"x": x}, attn_fn)
    # direct execution
    env2 = {"x": x}
    for name in g.order:
        op = g.ops[name]
        if op.kind == "input":
            continue
        if op.kind == "attention":
            env2[name] = attn_fn(name, env2)
        else:
            env2[name] = op.fn(*[env2[i] for i in op.inputs])
    np.testing.assert_allclose(env[prev], env2[prev], atol=1e-5)
    # every slice's program respects dependencies
    for sl in sp.slices:
        seen = set(sl.context_in) | {"x"}
        if sl.recv_attn:
            seen.add(sl.recv_attn)
        for name in sl.program:
            for inp in g.ops[name].inputs:
                assert inp in seen or inp in sl.program[:sl.program.index(
                    name)], (name, inp)
            seen.add(name)


# ---------------------------------------------------------------------------
# checkpoint dtype preservation across the whole config space
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["rwkv6-7b", "zamba2-1.2b",
                                  "seamless-m4t-medium"])
def test_checkpoint_preserves_structure(arch, tmp_path):
    from repro.models import transformer
    from repro.training import checkpoint as ckpt
    cfg = registry.get_smoke_config(arch).replace(dtype=jnp.bfloat16)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    ckpt.save(str(tmp_path), params, None, step=1)
    tree, _ = ckpt.restore(str(tmp_path), {"params": params})
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(tree["params"])):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
