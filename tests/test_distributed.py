"""Multi-device integration (subprocesses with 8 fake devices — XLA_FLAGS
must precede jax import, so these cannot run in the pytest process)."""
import pytest

pytestmark = pytest.mark.slow


def test_sharded_search_exact_and_statistical(multidevice):
    multidevice("""
import jax, jax.numpy as jnp
from repro import compat
from repro.core import binary, engine
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
key = jax.random.PRNGKey(0)
d, N, Q, k = 128, 4096, 8, 16
bits = jax.random.bernoulli(key, 0.5, (N, d)).astype(jnp.uint8)
qbits = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (Q, d)).astype(jnp.uint8)
packed, qp = binary.pack_bits(bits), binary.pack_bits(qbits)
ed, ei = engine.search_chunked(packed, qp, k, d)
cs = engine.shard_datastore(packed, mesh, ("pod", "data", "model"))
with mesh:
    sd, si = jax.jit(lambda c, q: engine.search_sharded(c, q, k, d, mesh, ("pod","data","model")))(cs, qp)
assert (sd == ed).all() and (si == ei).all(), "exact sharded mismatch"
with mesh:
    ad, ai = jax.jit(lambda c, q: engine.search_sharded(c, q, k, d, mesh, ("pod","data","model"), k_local=4))(cs, qp)
recall = float(jnp.mean(jnp.any(ai[:, :, None] == ei[:, None, :], axis=1)))
assert recall > 0.9, recall
print("OK", recall)
""")


def test_moe_ep_matches_reference(multidevice):
    multidevice("""
import dataclasses, jax, jax.numpy as jnp
from repro import compat
from repro.configs import get_config, scaled_down
from repro.models import moe as moe_mod
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = scaled_down(get_config("kimi-k2-1t-a32b"))
cfg = dataclasses.replace(cfg, dtype="float32",
    moe=dataclasses.replace(cfg.moe, num_experts=8, experts_per_token=2, capacity_factor=8.0))
params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32) * 0.1
y_ref, _ = moe_mod.moe_forward(params, cfg, x, mesh=None)
with mesh:
    y_a2a, _ = jax.jit(lambda p, xx: moe_mod.moe_forward(p, cfg, xx, mesh=mesh,
        dp_axes=("pod","data"), strategy="a2a"))(params, x)
    y_ag, _ = jax.jit(lambda p, xx: moe_mod.moe_forward(p, cfg, xx, mesh=mesh,
        dp_axes=("pod","data"), strategy="allgather"))(params, x)
assert float(jnp.max(jnp.abs(y_a2a - y_ref))) < 1e-5
assert float(jnp.max(jnp.abs(y_ag - y_ref))) < 1e-5
print("OK")
""")


def test_train_loss_decreases_and_ckpt_resume(multidevice):
    multidevice("""
import tempfile, jax, jax.numpy as jnp
from repro import compat
from repro.configs import get_config, scaled_down, TrainConfig
from repro.runtime import trainer
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = scaled_down(get_config("internlm2-20b"), d_model=64, d_ff=128, vocab_size=256)
tc = TrainConfig(total_steps=10, warmup_steps=1, learning_rate=1e-2)
with tempfile.TemporaryDirectory() as tmp:
    try:
        trainer.train(cfg, tc, mesh, seq_len=32, global_batch=8,
                      ckpt_dir=tmp, ckpt_every=2, log_every=100, preempt_at=5)
        raise SystemExit("expected preemption")
    except trainer.PreemptionError:
        pass
    rep = trainer.train(cfg, tc, mesh, seq_len=32, global_batch=8,
                        ckpt_dir=tmp, ckpt_every=2, log_every=100)
    assert rep.resumed_from == 5, rep.resumed_from
    assert rep.final_loss < 5.55, rep.final_loss
print("OK")
""")


def test_serve_step_with_retrieval_all_archs(multidevice):
    multidevice("""
import jax, jax.numpy as jnp
from repro import compat
from repro.configs import get_config, scaled_down
from repro.models import lm
from repro.dist import steps, sharding
from repro.core import retrieval
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
for name in ["gemma-2b", "zamba2-2.7b", "rwkv6-1.6b", "arctic-480b"]:
    cfg = scaled_down(get_config(name), d_model=64, d_ff=128, vocab_size=256)
    S = 64
    with mesh:
        serve_fn, pspecs, sspecs = steps.make_serve_step(cfg, mesh, S)
        params = jax.jit(lambda: lm.init_params(jax.random.PRNGKey(0), cfg),
                         out_shardings=sharding.named(mesh, pspecs))()
        state = jax.jit(lambda: lm.init_decode_state(cfg, 8, S),
                        out_shardings=sharding.named(mesh, sspecs))()
    store = retrieval.synthetic_datastore(cfg)
    store = jax.device_put(store, sharding.named(mesh, sharding.datastore_specs(mesh)))
    token = jnp.zeros((8, 1), jnp.int32)
    active = jnp.ones((8,), bool)
    logits, state, outs = serve_fn(params, token, state, active, store)
    assert outs["dists"].shape == (8, cfg.retrieval.k), name
    assert bool(jnp.isfinite(logits).all()), name
print("OK")
""")


def test_elastic_restore_different_mesh(multidevice):
    multidevice("""
import tempfile, jax, jax.numpy as jnp
from repro import compat
from repro.configs import get_config, scaled_down
from repro.models import lm
from repro.dist import sharding
from repro.checkpoint import manager as ckpt
cfg = scaled_down(get_config("gemma-2b"), d_model=64, d_ff=128, vocab_size=256)
mesh_a = compat.make_mesh((4, 2), ("data", "model"))
mesh_b = compat.make_mesh((2, 4), ("data", "model"))
pa = sharding.named(mesh_a, sharding.param_specs(cfg, mesh_a))
pb = sharding.named(mesh_b, sharding.param_specs(cfg, mesh_b))
with mesh_a:
    params = jax.jit(lambda: lm.init_params(jax.random.PRNGKey(0), cfg),
                     out_shardings=pa)()
with tempfile.TemporaryDirectory() as tmp:
    ckpt.save(tmp, 0, params)
    restored = ckpt.restore(tmp, 0, params, pb)   # elastic: new mesh layout
    a = jnp.asarray(jax.tree_util.tree_leaves(params)[0], jnp.float32)
    b = jnp.asarray(jax.tree_util.tree_leaves(restored)[0], jnp.float32)
    assert (a == b).all()
print("OK")
""")
