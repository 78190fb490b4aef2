"""Checkpoint store (atomic/keep-k/async/elastic), optimizer, data pipeline."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.data import Prefetcher, TokenStream
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         cosine_warmup, ef_int8_allreduce, ef_state_init)


def tree():
    return dict(a=jnp.arange(6.0).reshape(2, 3),
                b=dict(c=jnp.ones((4,), jnp.int32), d=jnp.float32(2.5)),
                e=[jnp.zeros((2,)), jnp.ones((3,))])


# --------------------------------------------------------------------------
# Checkpointing
# --------------------------------------------------------------------------

def test_roundtrip(tmp_path):
    t = tree()
    save_checkpoint(str(tmp_path), 3, t, meta=dict(cursor=7))
    t2, step, meta = load_checkpoint(str(tmp_path), t)
    assert step == 3 and meta["cursor"] == 7
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), t, t2)


def test_keep_k_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree())
    names = sorted(os.listdir(tmp_path))
    assert names == ["step_00000003", "step_00000004"]
    assert mgr.latest_step() == 4


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save_async(5, tree(), meta=dict(x=1))
    mgr.wait()
    t2, step, meta = mgr.restore(tree())
    assert step == 5 and meta["x"] == 1


def test_torn_write_ignored(tmp_path):
    """A .tmp directory without manifest must not count as a checkpoint."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, tree())
    os.makedirs(tmp_path / "step_00000002.tmp")
    # un-committed (no manifest) directory
    os.makedirs(tmp_path / "step_00000003")
    assert mgr.latest_step() == 1


def test_elastic_reshard(tmp_path):
    """Restore with explicit shardings (1-device mesh ≅ re-shard path)."""
    t = tree()
    save_checkpoint(str(tmp_path), 1, t)
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()), t)
    t2, _, _ = load_checkpoint(str(tmp_path), t, shardings=sh)
    for leaf in jax.tree.leaves(t2):
        assert isinstance(leaf, jax.Array)


def test_train_restart_bitexact(tmp_path):
    """Kill at step k, resume from checkpoint ⇒ same final params as
    uninterrupted run (fault-tolerance contract)."""
    from repro.launch.train import train

    ck1 = str(tmp_path / "a")
    full = train("two-tower-retrieval", steps=8, ckpt_dir=ck1, ckpt_every=4)

    ck2 = str(tmp_path / "b")
    with pytest.raises(RuntimeError):
        train("two-tower-retrieval", steps=8, ckpt_dir=ck2, ckpt_every=4,
              fail_at_step=6)
    resumed = train("two-tower-retrieval", steps=8, ckpt_dir=ck2,
                    ckpt_every=4, resume=True)
    assert resumed["restored_from"] == 4
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6),
        full["params"], resumed["params"])


# --------------------------------------------------------------------------
# Optimizer
# --------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    params = dict(x=jnp.asarray([5.0, -3.0]))
    opt = adamw_init(params)
    cfg = AdamWConfig(weight_decay=0.0, clip_norm=100.0)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(
            lambda q: jnp.sum(jnp.square(q["x"] - 1.0)))(p)
        p, o = adamw_update(p, g, o, jnp.float32(0.1), cfg)
        return p, o, loss

    for _ in range(300):
        params, opt, loss = step(params, opt)
    np.testing.assert_allclose(np.asarray(params["x"]), [1.0, 1.0], atol=1e-2)


def test_adamw_grad_clip():
    params = dict(x=jnp.asarray([0.0]))
    opt = adamw_init(params)
    cfg = AdamWConfig(clip_norm=1.0, weight_decay=0.0)
    g = dict(x=jnp.asarray([1e6]))
    p2, _ = adamw_update(params, g, opt, jnp.float32(0.1), cfg)
    assert abs(float(p2["x"][0])) < 0.2     # clipped step ≈ lr


def test_cosine_warmup_shape():
    lrs = [float(cosine_warmup(s, peak_lr=1.0, warmup=10, total=100))
           for s in range(100)]
    assert lrs[0] == 0.0
    assert abs(lrs[10] - 1.0) < 1e-6
    assert lrs[99] < 0.2
    assert all(b <= a + 1e-6 for a, b in zip(lrs[10:], lrs[11:]))


def test_ef_int8_allreduce_error_feedback():
    """Quantisation residual is carried: two steps of the same grad average
    to the true value much better than one-shot int8."""
    from functools import partial
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    g = dict(w=jnp.asarray(np.linspace(-1, 1, 256), jnp.float32) * 0.01)
    ef = ef_state_init(g)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), P()), check_vma=False)
    def run(gg, ee):
        return ef_int8_allreduce(gg, ee, axis_name="data")

    out1, ef = run(g, ef)
    out2, ef = run(g, ef)
    avg = (np.asarray(out1["w"]) + np.asarray(out2["w"])) / 2
    np.testing.assert_allclose(avg, np.asarray(g["w"]), atol=2e-4)


# --------------------------------------------------------------------------
# Data pipeline
# --------------------------------------------------------------------------

def test_token_stream_determinism():
    s1 = TokenStream(vocab=1000, seq_len=16, global_batch=4, seed=7)
    s2 = TokenStream(vocab=1000, seq_len=16, global_batch=4, seed=7)
    a, ta = s1.batch(12)
    b, tb = s2.batch(12)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(ta, tb)
    assert a.shape == (4, 16) and a.max() < 1000 and a.min() >= 0
    # targets are the shifted stream
    c, _ = s1.batch(13)
    assert not np.array_equal(a, c)


def test_prefetcher_order_and_close():
    pf = Prefetcher(lambda step: step * step, depth=2, num_steps=5)
    got = [(s, v) for s, v in pf]
    assert got == [(i, i * i) for i in range(5)]
    pf.close()


def test_prefetcher_propagates_errors():
    def boom(step):
        if step == 2:
            raise ValueError("bad shard")
        return step

    pf = Prefetcher(boom, depth=1, num_steps=5)
    with pytest.raises(ValueError, match="bad shard"):
        list(pf)
