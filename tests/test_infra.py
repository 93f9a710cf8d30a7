"""Infrastructure: optimizer, checkpoint atomicity/resume, data determinism,
fault tolerance logic, compression, streaming messages, HLO cost walker."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_matches_reference_math():
    from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                      grad_clip=0.0)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.25])}
    s = init_opt_state(p)
    newp, news, m = adamw_update(p, g, s, cfg)
    mu = 0.1 * np.asarray([0.5, 0.25])
    nu = 0.01 * np.asarray([0.25, 0.0625])
    mhat = mu / (1 - 0.9)
    nhat = nu / (1 - 0.99)
    want = np.asarray([1.0, -2.0]) - 0.1 * mhat / (np.sqrt(nhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(newp["w"]), want, rtol=1e-6)
    assert int(news["step"]) == 1


def test_grad_clip_caps_update():
    from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state, \
        global_norm
    p = {"w": jnp.ones(4)}
    g = {"w": jnp.full(4, 100.0)}
    s = init_opt_state(p)
    cfg = AdamWConfig(grad_clip=1.0, weight_decay=0.0)
    _, news, m = adamw_update(p, g, s, cfg)
    assert float(m["grad_norm"]) == pytest.approx(200.0)
    # clipped first moment: |mu| <= (1-b1) * clip_scaled grad
    assert float(jnp.abs(news["mu"]["w"]).max()) <= 0.1 * 0.5 + 1e-6


def test_warmup_cosine_shape():
    from repro.optim.schedules import warmup_cosine
    f = warmup_cosine(1.0, 10, 100)
    assert float(f(jnp.asarray(0))) == 0.0
    assert float(f(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(f(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-3)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_bitwise(tmp_path):
    from repro.ckpt.checkpoint import restore_checkpoint, save_checkpoint
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones(3, jnp.bfloat16),
                  "d": jnp.asarray(7, jnp.int32)}}
    save_checkpoint(str(tmp_path), 5, tree, extra={"data": {"step": 5}})
    got, step, extra = restore_checkpoint(str(tmp_path), tree)
    assert step == 5 and extra == {"data": {"step": 5}}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_torn_checkpoint_ignored(tmp_path):
    from repro.ckpt.checkpoint import latest_step, save_checkpoint
    tree = {"a": jnp.ones(2)}
    save_checkpoint(str(tmp_path), 1, tree)
    torn = tmp_path / "step_000000002"
    (torn / "arrays").mkdir(parents=True)
    (torn / "meta.json").write_text(json.dumps({"step": 2}))
    # no COMMIT marker -> must be ignored
    assert latest_step(str(tmp_path)) == 1


def test_checkpoint_keep_policy(tmp_path):
    from repro.ckpt.checkpoint import latest_step, save_checkpoint
    tree = {"a": jnp.ones(2)}
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, tree, keep=2)
    steps = sorted(p.name for p in tmp_path.glob("step_*"))
    assert len(steps) == 2 and latest_step(str(tmp_path)) == 5


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_determinism_and_resume():
    from repro.data.pipeline import DataConfig, TokenPipeline
    cfg = DataConfig(vocab=101, seq_len=16, global_batch=4, seed=7)
    p1 = TokenPipeline(cfg)
    seq = [p1.next_batch() for _ in range(3)]
    p2 = TokenPipeline(cfg)
    p2.restore({"step": 2})
    b2 = p2.next_batch()
    np.testing.assert_array_equal(seq[2]["tokens"], b2["tokens"])
    np.testing.assert_array_equal(seq[2]["labels"], b2["labels"])


def test_data_dp_ranks_differ():
    from repro.data.pipeline import DataConfig, TokenPipeline
    a = TokenPipeline(DataConfig(vocab=50, seq_len=8, global_batch=8,
                                 dp_rank=0, dp_size=2)).next_batch()
    b = TokenPipeline(DataConfig(vocab=50, seq_len=8, global_batch=8,
                                 dp_rank=1, dp_size=2)).next_batch()
    assert a["tokens"].shape == (4, 8)
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_labels_are_next_tokens():
    from repro.data.pipeline import DataConfig, TokenPipeline
    b = TokenPipeline(DataConfig(vocab=64, seq_len=12, global_batch=2)
                      ).next_batch()
    # structure: mostly label[t] == (31*token[t]+7) % V (90% of positions)
    match = (b["labels"] == (b["tokens"] * 31 + 7) % 64).mean()
    assert match > 0.7


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_detects_dead():
    from repro.ft.fault_tolerance import HeartbeatMonitor
    t = [0.0]
    mon = HeartbeatMonitor(3, timeout_s=10, clock=lambda: t[0])
    for r in range(3):
        mon.beat(r, 1)
    t[0] = 5.0
    mon.beat(0, 2)
    mon.beat(1, 2)
    t[0] = 12.0
    assert mon.dead_ranks() == [2]


def test_straggler_detector():
    from repro.ft.fault_tolerance import StragglerDetector
    det = StragglerDetector(4, window=5, threshold=1.5)
    for _ in range(5):
        for r in range(3):
            det.record(r, 1.0)
        det.record(3, 3.0)
    assert det.stragglers() == [3]


@given(devs=st.integers(16, 600), gb=st.sampled_from([128, 256, 512]))
@settings(max_examples=40, deadline=None)
def test_elastic_mesh_invariant(devs, gb):
    from repro.ft.fault_tolerance import solve_elastic_mesh
    plan = solve_elastic_mesh(devs, model_parallel=16, global_batch=gb)
    dp = plan.mesh_shape[0]
    assert dp * 16 <= devs
    assert dp * plan.per_device_batch * plan.grad_accum == gb
    assert plan.per_device_batch <= 64
    assert plan.dropped_devices == devs - dp * 16


def test_preemption_guard(tmp_path):
    import signal

    from repro.ft.fault_tolerance import PreemptionGuard
    g = PreemptionGuard().install()
    os.kill(os.getpid(), signal.SIGTERM)
    assert g.requested
    g.uninstall()


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bound():
    from repro.distributed.compression import quantize_int8, dequantize_int8
    x = jax.random.normal(jax.random.PRNGKey(0), (256,))
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x).max()
    assert float(err) <= float(s) / 2 + 1e-7


def test_error_feedback_reduces_bias():
    from repro.distributed.compression import ErrorFeedback
    g = {"w": jnp.full((64,), 0.003)}     # below one int8 quantum of amax
    res = ErrorFeedback.init(g)
    total = jnp.zeros(64)
    for _ in range(20):
        ghat, res = ErrorFeedback.apply(g, res)
        total = total + ghat["w"]
    # with error feedback, the accumulated signal approaches 20*g
    np.testing.assert_allclose(np.asarray(total), 0.06 * np.ones(64),
                               rtol=0.15)


# ---------------------------------------------------------------------------
# streaming messages (paper artifact)
# ---------------------------------------------------------------------------

@given(op=st.integers(0, 10), row=st.integers(0, 255),
       col=st.integers(0, 255), flags=st.integers(0, 255),
       payload=st.integers(0, 2**32 - 1))
@settings(max_examples=80, deadline=None)
def test_message_pack_roundtrip(op, row, col, flags, payload):
    from repro.core.streaming import Message, Opcode, decode, encode
    m = Message(Opcode(op), row, col, flags, payload)
    assert decode(encode(m)) == m


def test_stream_counts_match_enumeration():
    from repro.core.folds import PEArray, decompose
    from repro.core.loopnest import ConvLoopNest
    from repro.core.streaming import fold_stream, stream_counts
    cv = ConvLoopNest(n=1, nf=4, c=4, r=3, s=3, x=5, y=5, stride=1, pad=1)
    plan = decompose(cv, PEArray(4, 24))
    enumerated = {}
    for fold in plan.filter_folds():
        for msg in fold_stream(plan, fold):
            enumerated[msg.opcode.name] = enumerated.get(msg.opcode.name,
                                                         0) + 1
    counts = stream_counts(plan)
    for k, v in enumerated.items():
        assert counts[k] == v, (k, counts[k], v)


# ---------------------------------------------------------------------------
# HLO cost walker
# ---------------------------------------------------------------------------

def test_hlo_walker_scales_loops():
    from repro.hlo_cost import analyze_hlo

    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((32, 64), jnp.float32),
                         jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
                         ).compile()
    cost = analyze_hlo(c.as_text())
    want = 12 * 2 * 32 * 64 * 64
    assert want <= cost.flops <= 1.2 * want
    assert cost.trip_counts and list(cost.trip_counts.values())[0] == 12
