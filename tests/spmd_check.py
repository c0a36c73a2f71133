"""Executed in a subprocess with 8 forced host devices (see test_spmd.py).

Numerically verifies the distributed paths against single-device oracles:
  1. sharded (shard_map) embedding == jnp.take
  2. expert-parallel MoE == tensor-parallel MoE (same routing)
  3. HWA train+sync steps on a (2,2,2) mesh == single-device HWA
  4. a full train_step lowers, compiles AND RUNS on the test mesh
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core.hwa import HWAConfig
from repro.launch.mesh import make_test_mesh
from repro.launch.specs import input_specs
from repro.launch.steps import (make_hwa_sync_step, make_hwa_train_step,
                                make_train_step)
from repro.models.registry import _sharded_gather, build_model
from repro.models.types import InputShape
from repro.sharding.rules import make_tp_rules

ok = True


def check(name, cond):
    global ok
    print(("PASS " if cond else "FAIL ") + name)
    ok = ok and cond


# ---- 1. sharded embedding ------------------------------------------------
mesh = make_test_mesh((2, 4), ("data", "model"))
rules = make_tp_rules(mesh)
emb = jax.random.normal(jax.random.key(0), (32, 16))
ids = jax.random.randint(jax.random.key(1), (4, 6), 0, 32)
with mesh:
    got = jax.jit(lambda e, i: _sharded_gather(e, i, rules))(emb, ids)
want = jnp.take(emb, ids, axis=0)
check("sharded_gather == take",
      bool(jnp.max(jnp.abs(got - want)) < 1e-6))

# ---- 2. EP MoE == TP MoE --------------------------------------------------
from repro.models.moe import init_moe, moe_forward, moe_forward_ep

cfg = get_smoke_config("granite-moe-1b-a400m")  # 4 experts % 4 == 0
p, _ = init_moe(cfg, jax.random.key(0), jnp.float32)
x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
want, aux_w = moe_forward(cfg, p, x)
with mesh:
    got, aux_g = jax.jit(lambda p, x: moe_forward_ep(
        cfg, p, x, mesh=mesh, capacity_factor=4.0))(p, x)
check("EP MoE == TP MoE",
      bool(jnp.max(jnp.abs(got - want)) < 1e-3))
# EP computes the load-balance loss per data shard then pmeans — a
# (standard) estimator of the global loss, not identical to it.
check("EP aux ~= TP aux", abs(float(aux_g) - float(aux_w)) < 0.25)

# ---- 3+4. HWA steps on a mesh vs single device ----------------------------
mesh3 = make_test_mesh((2, 2, 2), ("replica", "data", "model"))
rules3 = make_tp_rules(mesh3, replica_axis="replica")
cfg_lm = get_smoke_config("granite-3-2b")
lm = build_model(cfg_lm)
shape = InputShape("tiny", seq_len=16, global_batch=8, kind="train")
specs, dims = input_specs(cfg_lm, shape)
hwa_cfg = HWAConfig(n_replicas=2, window=3)
bundle = make_hwa_train_step(lm, rules3, specs, dims, hwa_cfg,
                             optimizer="sgd", lr=0.1)
compiled = bundle.lower(mesh3).compile()
check("hwa_train_step compiles on (2,2,2) mesh", True)

params = lm.init(jax.random.key(0))
K = 2
stacked = jax.tree.map(lambda x: jnp.stack([x, x]), params)
from repro.optim import sgd as mk_sgd
opt = mk_sgd(momentum=0.9, weight_decay=5e-4)
opt_state = jax.vmap(opt.init)(stacked)
batch = {
    "tokens": jax.random.randint(jax.random.key(2), (K, 8, 16), 0,
                                 cfg_lm.vocab_size),
    "targets": jax.random.randint(jax.random.key(3), (K, 8, 16), 0,
                                  cfg_lm.vocab_size),
}
with mesh3:
    new_stacked, new_opt, loss = compiled(stacked, opt_state, batch)
check("hwa_train_step runs; finite loss", bool(jnp.isfinite(loss)))

# single-device oracle: vmap'd steps
def one(params, opt_state, b):
    def loss_fn(p):
        return lm.loss(p, b)
    (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
    upd, opt_state = opt.update(g, opt_state, params, 0.1)
    from repro.optim import apply_updates
    return apply_updates(params, upd), opt_state, l

ref_stacked, _, ref_loss = jax.vmap(one)(
    jax.tree.map(lambda x: jnp.stack([x, x]), params),
    jax.vmap(opt.init)(jax.tree.map(lambda x: jnp.stack([x, x]), params)),
    batch)
err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32))))
          for a, b in zip(jax.tree.leaves(new_stacked),
                          jax.tree.leaves(ref_stacked)))
check(f"mesh HWA step == single-device vmap (err={err:.2e})", err < 5e-3)

# sync step
sync = make_hwa_sync_step(lm, rules3, hwa_cfg)
sync_c = sync.lower(mesh3).compile()
I = hwa_cfg.window
spec = sync.pack_spec               # window state is packed (I, P)/(P,)
ring = jnp.zeros((I, spec.padded), jnp.float32)
total = jnp.zeros((spec.padded,), jnp.float32)
zero = jnp.zeros((), jnp.int32)
with mesh3:
    out = sync_c(new_stacked, ring, total, zero, zero)
new_inner, _, _, count, nidx, wa = out
check("sync: replicas equal after restart",
      bool(jnp.max(jnp.abs(jax.tree.leaves(new_inner)[0][0]
                           - jax.tree.leaves(new_inner)[0][1])) == 0))
check("sync: window count advanced", int(count) == 1)

# plain train step lowers+runs too: fsdp, sequence_parallel, and both
shape2 = InputShape("tiny2", seq_len=16, global_batch=4, kind="train")
specs2, dims2 = input_specs(cfg_lm, shape2)
opt2 = mk_sgd(momentum=0.9, weight_decay=5e-4)
os2 = opt2.init(params)
batch2 = {"tokens": batch["tokens"][0, :4], "targets": batch["targets"][0, :4]}
for label, kw in [("fsdp", dict(fsdp=True)),
                  ("seq-parallel", dict(sequence_parallel=True)),
                  ("fsdp+seq-parallel", dict(fsdp=True,
                                             sequence_parallel=True))]:
    rules2 = make_tp_rules(mesh, **kw)
    b2 = make_train_step(lm, rules2, specs2, dims2, optimizer="sgd")
    c2 = b2.lower(mesh).compile()
    with mesh:
        # fresh copies: the step donates params + opt state
        p2, o2, m2 = c2(jax.tree.map(jnp.array, params),
                        jax.tree.map(jnp.array, os2), batch2)
    check(f"plain train_step ({label}) runs on (2,4) mesh",
          bool(jnp.isfinite(m2["loss"])))

print("ALL_OK" if ok else "SOME_FAILED")
raise SystemExit(0 if ok else 1)
