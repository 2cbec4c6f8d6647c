"""Rotational staggered pipelining (paper §4.3): schedule properties proven
for swept (n, steps) and the executable rotation demo."""
import jax
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.core import converter, pipeline
from repro.models import blocks


@settings(deadline=None, max_examples=40)
@given(n=st.integers(2, 10), steps=st.integers(1, 50))
def test_schedule_properties(n, steps):
    s = pipeline.rotational_schedule(n, steps)
    v = pipeline.validate(s)
    assert v["conflict_free"], (n, steps)
    assert v["sequential"], (n, steps)
    assert v["attn_bubble_free"], (n, steps)


def test_rotation_law():
    s = pipeline.rotational_schedule(5, 8)
    for e in s.events:
        if e.device.startswith("model:"):
            assert e.device == f"model:{(e.batch + e.step) % 4}"


def test_steady_state_utilisation_approaches_one():
    u = pipeline.utilisation(pipeline.rotational_schedule(4, 200))
    assert u["attn"] > 0.98
    for r in range(3):
        assert u[f"model:{r}"] > 0.98


def test_throughput_speedup_monotone():
    # n/(n-1): biggest win at n=2, approaching 1 from above
    prev = float("inf")
    for n in range(2, 10):
        s = pipeline.throughput_speedup(n)
        assert 1.0 < s <= 2.0
        assert s < prev
        prev = s


def test_run_rotational_executes_correctly():
    """n batches through real converter slices under the rotation order:
    results match direct execution, and the replica log obeys the law."""
    cfg = registry.get_smoke_config("llama3-8b")
    w = blocks.init_dense_block(jax.random.PRNGKey(0), cfg)
    n = 4
    progs, inputs, direct = [], [], []

    def attn_fn(j, name, env):
        v = env["v_proj"]
        return np.repeat(v, env["q_proj"].shape[1] // v.shape[1], axis=1)

    for j in range(n):
        g = converter.build_block_graph(cfg, weights=w, batch=2)
        sp = converter.split_at_attention(g)
        progs.append(sp)
        x = np.random.default_rng(j).standard_normal(
            (2, cfg.d_model)).astype(np.float32)
        inputs.append({"x": x})
        direct.append(sp.run({"x": x}, lambda nm, env: attn_fn(j, nm, env)))

    envs, log = pipeline.run_rotational(progs, inputs, attn_fn)
    for j in range(n):
        np.testing.assert_allclose(envs[j]["residual2"],
                                   direct[j]["residual2"], atol=1e-6)
    for j, k, replica in log:
        assert replica == (j + k) % (n - 1)
    # every (batch, slice) executed exactly once
    assert sorted({(j, k) for j, k, _ in log}) == \
        [(j, k) for j in range(n) for k in range(len(progs[0].slices))]
