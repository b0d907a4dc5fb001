"""Training loop, optimizer, data pipeline, checkpoint/restart, fault
tolerance, gradient compression."""

import dataclasses
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.data.synthetic import TokenStream
from repro.models import transformer as tfm
from repro.train.loop import make_train_step
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state


def _tiny():
    return dataclasses.replace(get_config("qwen2.5-3b").smoke(), dtype="float32")


def test_loss_decreases():
    cfg = _tiny()
    opt_cfg = OptConfig(lr=1e-2, warmup_steps=5)
    params = tfm.init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg, kv_chunk=32))
    stream = TokenStream(cfg.vocab_size, 32, 4, seed=7)
    losses = []
    batch = stream.batch_at(0)  # overfit one batch -> must decrease
    for i in range(25):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::6]
    assert int(m["skipped"]) == 0


def test_microbatch_equivalence():
    cfg = _tiny()
    opt_cfg = OptConfig(lr=0.0, weight_decay=0.0)
    params = tfm.init_params(cfg, jax.random.key(0))
    stream = TokenStream(cfg.vocab_size, 32, 8, seed=3)
    batch = stream.batch_at(0)
    s1 = make_train_step(cfg, opt_cfg, microbatches=1, kv_chunk=32)
    s4 = make_train_step(cfg, opt_cfg, microbatches=4, kv_chunk=32)
    o1 = init_opt_state(params, opt_cfg)
    o4 = init_opt_state(params, opt_cfg)
    _, _, m1 = jax.jit(s1)(params, o1, batch)
    _, _, m4 = jax.jit(s4)(params, o4, batch)
    assert np.isclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-4)
    assert np.isclose(float(m1["grad_norm"]), float(m4["grad_norm"]), rtol=1e-3)


def test_nan_guard_skips_bad_step():
    cfg = _tiny()
    opt_cfg = OptConfig(lr=1e-3)
    params = tfm.init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg, kv_chunk=32))
    stream = TokenStream(cfg.vocab_size, 32, 4)
    good = stream.batch_at(0)
    p1, o1, m1 = step(params, opt, good)
    # poison the params so the loss goes NaN
    bad_params = jax.tree.map(lambda x: x * jnp.nan, params)
    p2, o2, m2 = step(bad_params, o1, good)
    assert not np.isfinite(float(m2["loss"]))
    assert int(o2["skipped"]) == 1
    # params passed through unchanged (still NaN inputs, not updated)
    leaf_in = jax.tree.leaves(bad_params)[0]
    leaf_out = jax.tree.leaves(p2)[0]
    assert np.array_equal(
        np.isnan(np.asarray(leaf_in)), np.isnan(np.asarray(leaf_out))
    )


def test_adamw_moment_dtype_bf16():
    cfg = _tiny()
    opt_cfg = OptConfig(moment_dtype="bfloat16")
    params = tfm.init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params, opt_cfg)
    assert jax.tree.leaves(opt["mu"])[0].dtype == jnp.bfloat16
    grads = jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32) * 0.01, params)
    p2, o2, gn = adamw_update(grads, opt, params, opt_cfg)
    assert jax.tree.leaves(o2["nu"])[0].dtype == jnp.bfloat16
    assert float(gn) > 0


# ---------------------------------------------------------------------------
# Data pipeline determinism
# ---------------------------------------------------------------------------


def test_stream_deterministic_and_resumable():
    s1 = TokenStream(1000, 16, 8, seed=5)
    s2 = TokenStream(1000, 16, 8, seed=5)
    b1 = s1.host_batch_at(42)
    b2 = s2.host_batch_at(42)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    full = s1.host_batch_at(3)
    assert (full["labels"][:, :-1] == full["tokens"][:, 1:]).all()
    # shard slices reassemble the global batch for any shard count
    for n_shards in (2, 4):
        parts = [s1.shard_batch_at(7, k, n_shards)["tokens"] for k in range(n_shards)]
        np.testing.assert_array_equal(np.concatenate(parts), s1.host_batch_at(7)["tokens"])


# ---------------------------------------------------------------------------
# Checkpoint + fault tolerance
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    ckpt = pytest.importorskip("repro.dist.checkpoint")

    tree = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "nest": {"b": jnp.ones((2,), jnp.int32)},
        "tup": (jnp.zeros(3), jnp.full((2, 2), 7.0)),
    }
    path = ckpt.save(str(tmp_path), 5, tree, extra={"note": "x"})
    assert os.path.exists(path)
    assert ckpt.latest_step(str(tmp_path)) == 5
    restored, step, extra = ckpt.restore(str(tmp_path))
    assert step == 5 and extra["note"] == "x"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restart_is_bitwise_resumable(tmp_path):
    """Kill/restart: 10 straight steps == 5 steps + save + restore + 5."""
    ckpt = pytest.importorskip("repro.dist.checkpoint")

    cfg = _tiny()
    opt_cfg = OptConfig(lr=1e-3)
    stream = TokenStream(cfg.vocab_size, 32, 4, seed=1)
    step = jax.jit(make_train_step(cfg, opt_cfg, kv_chunk=32))

    def run(params, opt, lo, hi):
        for i in range(lo, hi):
            params, opt, m = step(params, opt, stream.batch_at(i))
        return params, opt, m

    p0 = tfm.init_params(cfg, jax.random.key(0))
    o0 = init_opt_state(p0, opt_cfg)
    pa, oa, ma = run(p0, o0, 0, 10)

    pb, ob, _ = run(p0, o0, 0, 5)
    ckpt.save(str(tmp_path), 5, (pb, ob))
    (pr, orr), s, _ = ckpt.restore(str(tmp_path))
    assert s == 5
    pc, oc, mc = run(pr, orr, 5, 10)
    np.testing.assert_allclose(float(ma["loss"]), float(mc["loss"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_run_resilient_recovers_from_injected_failure(tmp_path):
    fault = pytest.importorskip("repro.dist.fault")
    ElasticMesh, run_resilient = fault.ElasticMesh, fault.run_resilient

    cfg = _tiny()
    opt_cfg = OptConfig(lr=1e-3)
    stream = TokenStream(cfg.vocab_size, 32, 4, seed=2)
    fail_at = {7}

    def failure_hook(step):
        if step in fail_at:
            fail_at.clear()  # fail once
            raise RuntimeError("injected device loss")

    def make_state(mesh):
        p = tfm.init_params(cfg, jax.random.key(0))
        return p, init_opt_state(p, opt_cfg)

    def make_step(mesh):
        return jax.jit(make_train_step(cfg, opt_cfg, kv_chunk=32))

    report = run_resilient(
        total_steps=12,
        ckpt_dir=str(tmp_path),
        make_state=make_state,
        make_step=make_step,
        batch_for=stream.batch_at,
        shardings_for=lambda mesh, s: None,
        ckpt_every=5,
        failure_hook=failure_hook,
        elastic=ElasticMesh(model_degree=1),
    )
    assert report.restarts == 1
    assert report.final_step == 12
    # restart resumed from step 5, so total steps run = 12 + (7 - 5)
    assert report.steps_run == 14


def test_watchdog_flags_straggler():
    fault = pytest.importorskip("repro.dist.fault")
    StepWatchdog, StragglerTimeout = fault.StepWatchdog, fault.StragglerTimeout

    wd = StepWatchdog(deadline_factor=3.0, warmup=3)
    for _ in range(6):
        wd.check(0.1)
    with pytest.raises(StragglerTimeout):
        wd.check(1.0)


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------


def test_compression_error_feedback_bounds():
    compress = pytest.importorskip("repro.dist.compress")
    compress_leaf, dequantize = compress.compress_leaf, compress.dequantize

    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((300,)) * 0.01, jnp.float32)
    err = jnp.zeros_like(g)
    (q, scale), err2 = compress_leaf(g, err)
    deq = dequantize(q, scale, g.size, g.shape, jnp.float32)
    # reconstruction + error == original (error feedback identity)
    np.testing.assert_allclose(np.asarray(deq + err2), np.asarray(g), rtol=1e-5, atol=1e-7)
    # quantization error bounded by scale/2 per element
    per_block_scale = np.asarray(scale).ravel()
    assert np.abs(np.asarray(err2)).max() <= per_block_scale.max() * 0.5 + 1e-8


def test_pod_sum_compressed_matches_psum():
    pytest.importorskip("repro.dist.compress")
    from tests.conftest import run_multidevice

    code = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.dist.compress import compressed_grad_sync, init_error_tree

devs = np.asarray(jax.devices()).reshape(4)
mesh = Mesh(devs, ("pod",))
g = jnp.asarray(np.random.default_rng(0).standard_normal((4, 512)), jnp.float32)

def f(g_local):
    grads = {"w": g_local[0]}
    err = init_error_tree(grads)
    synced, _ = compressed_grad_sync(grads, err, axis="pod")
    return synced["w"][None]

out = jax.shard_map(f, mesh=mesh, in_specs=P("pod", None), out_specs=P("pod", None))(g)
ref = np.mean(np.asarray(g), axis=0)
got = np.asarray(out)[0]
rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
assert rel < 2e-2, rel
print("COMPRESS_OK", rel)
"""
    out = run_multidevice(code, n_devices=4, x64=False)
    assert "COMPRESS_OK" in out


def test_compression_ratio():
    compression_ratio = pytest.importorskip("repro.dist.compress").compression_ratio

    assert compression_ratio(4) < 0.26  # ~8x less than f32
