"""Trainer, checkpointing, fault tolerance, optimizer, compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.synthetic import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         compress_int8, decompress_int8, cosine_schedule,
                         wsd_schedule, ef_compress)
from repro.train import (CheckpointManager, Heartbeat, StragglerMonitor,
                         TrainConfig, Trainer)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_decreases_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = adamw_update(grads, state, params, lr=0.05,
                                     weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_wsd_schedule_shape():
    fn = wsd_schedule(1.0, warmup_steps=10, stable_steps=80, decay_steps=10)
    assert float(fn(0)) == 0.0
    assert float(fn(10)) == pytest.approx(1.0)
    assert float(fn(50)) == pytest.approx(1.0)      # stable plateau
    assert float(fn(100)) == pytest.approx(0.1, rel=0.05)


def test_cosine_schedule_monotone_decay():
    fn = cosine_schedule(1.0, 5, 100)
    vals = [float(fn(s)) for s in range(5, 100, 5)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(5.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# int8 error-feedback gradient compression
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bound(rng):
    g = jnp.asarray(rng.normal(size=(1000,)), jnp.float32)
    q, s = compress_int8(g)
    rec = decompress_int8(q, s, g.shape)
    # blockwise symmetric quantization: |err| <= scale/2 per block
    err = np.abs(np.asarray(rec - g))
    scales = np.repeat(np.asarray(s).reshape(-1), 256)[:1000]
    assert (err <= scales / 2 + 1e-7).all()


def test_error_feedback_accumulates():
    g = jnp.full((256,), 1e-4, jnp.float32)   # below quantization step alone
    residual = jnp.zeros((256,), jnp.float32)
    total = jnp.zeros((256,), jnp.float32)
    for _ in range(50):
        q, s, residual = ef_compress(g, residual)
        total = total + decompress_int8(q, s, g.shape)
    # EF: the long-run average transmitted equals the true gradient
    np.testing.assert_allclose(np.asarray(total / 50),
                               np.asarray(g), rtol=0.2)


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------

@pytest.fixture
def ckpt_dir(tmp_path):
    return str(tmp_path / "ckpt")


def test_checkpoint_roundtrip(ckpt_dir):
    mgr = CheckpointManager(ckpt_dir)
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": {"c": jnp.ones((3, 4), jnp.bfloat16)}}
    mgr.save(7, tree, meta={"data_step": 7})
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    restored, meta = mgr.restore(like)
    assert meta["data_step"] == 7
    assert (np.asarray(restored["a"]) == np.arange(10)).all()
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_async_and_gc(ckpt_dir):
    mgr = CheckpointManager(ckpt_dir, keep=2)
    tree = {"w": jnp.zeros((4,))}
    for s in (1, 2, 3, 4):
        mgr.save_async(s, tree)
    mgr.wait()
    assert mgr.latest_step == 4
    steps = sorted(int(d[5:]) for d in os.listdir(ckpt_dir)
                   if d.startswith("step_"))
    assert steps == [3, 4]      # gc kept newest 2


def test_checkpoint_ignores_partial(ckpt_dir):
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(1, {"w": jnp.ones((2,))})
    # simulate a crash mid-save: step dir without manifest
    os.makedirs(os.path.join(ckpt_dir, "step_000000000099"))
    assert mgr.latest_step == 1


def test_checkpoint_shape_mismatch_raises(ckpt_dir):
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(1, {"w": jnp.ones((2,))})
    with pytest.raises(ValueError):
        mgr.restore({"w": jnp.ones((3,))})


# ---------------------------------------------------------------------------
# resilience
# ---------------------------------------------------------------------------

def test_straggler_monitor_flags_sustained_outliers():
    m = StragglerMonitor(min_samples=5, consecutive=3)
    flagged = False
    for _ in range(20):
        flagged |= m.record(1.0)
    assert not flagged
    m.record(5.0)
    m.record(5.0)
    assert not m.record(1.0)    # hysteresis resets on a good step
    for _ in range(2):
        m.record(5.0)
    assert m.record(5.0)        # 3 consecutive -> alarm


def test_heartbeat_detects_dead_host():
    hb = Heartbeat(timeout=10.0)
    hb.beat("host0", now=0.0)
    hb.beat("host1", now=5.0)
    assert hb.dead_hosts(now=12.0) == ["host0"]


# ---------------------------------------------------------------------------
# trainer end-to-end (1-device mesh)
# ---------------------------------------------------------------------------

def _mk_trainer(tmp, **tc_kw):
    cfg = get_config("qwen3-8b", smoke=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    tc = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=50,
                     checkpoint_every=5, checkpoint_dir=str(tmp), **tc_kw)
    return Trainer(cfg, tc, mesh, global_batch=8, seq_len=32)


def test_trainer_loss_decreases_and_resumes(tmp_path):
    tr = _mk_trainer(tmp_path / "c1")
    hist = tr.run(steps=10, log_every=0)
    assert len(hist) == 10
    assert all(np.isfinite(h["loss"]) for h in hist)
    # resume continues the step counter from the checkpoint
    tr2 = _mk_trainer(tmp_path / "c1")
    tr2.run(steps=2, log_every=0)
    assert tr2.step == 12


def test_grad_accum_matches_full_batch(tmp_path):
    """accum=2 over the same global batch gives (near-)identical updates."""
    t1 = _mk_trainer(tmp_path / "a", grad_accum=1)
    t2 = _mk_trainer(tmp_path / "b", grad_accum=2)
    h1 = t1.run(steps=3, log_every=0)
    h2 = t2.run(steps=3, log_every=0)
    for a, b in zip(h1, h2):
        assert a["loss"] == pytest.approx(b["loss"], rel=2e-3)


def test_compressed_grads_still_converge(tmp_path):
    tr = _mk_trainer(tmp_path / "c", compress_grads=True)
    hist = tr.run(steps=8, log_every=0)
    assert np.isfinite(hist[-1]["loss"])


def test_data_pipeline_deterministic_and_host_sharded():
    p = TokenPipeline(vocab_size=100, global_batch=8, seq_len=16, seed=1)
    a = p.batch(3)["tokens"]
    b = p.batch(3)["tokens"]
    assert (a == b).all()
    assert not (a == p.batch(4)["tokens"]).all()
    # host sharding partitions the global batch
    h0 = p.batch(3, host_id=0, n_hosts=2)["tokens"]
    h1 = p.batch(3, host_id=1, n_hosts=2)["tokens"]
    assert h0.shape[0] == 4 and h1.shape[0] == 4
    assert not (h0 == h1).all()
