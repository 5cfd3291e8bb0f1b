"""Multi-device integration: every arch takes a real train step AND a
seq-sharded decode step on a 4-device (1,2,2) mesh — catches FSDP
gather-axis and TP-psum bugs invisible on the (1,1,1) smoke mesh.

Runs in a subprocess because the parent pytest runs on 1 device (device
count locks at first jax init).
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
import repro
from repro.configs import list_archs
from repro.configs.reduced import reduced
from repro.models import model as M
from repro.serve import step as serve_step
from repro.train import optimizer
from repro.train.step import make_train_step

mesh = jax.make_mesh((1, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*3)
import dataclasses
from repro.configs import get_arch
for arch in list_archs():
    cfg = reduced(arch)
    # enable real TP on the 2-wide model axis (and 2-way FSDP); xlstm stays
    # tp_shard=False by design (DESIGN.md §Arch-applicability)
    tp_shard = get_arch(arch).tp_shard
    cfg = dataclasses.replace(cfg, tp=2, tp_shard=tp_shard, n_heads=4,
                              n_kv_heads=2 if cfg.n_kv_heads < cfg.n_heads
                              else 4, vocab_size=256)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = optimizer.init(params)
    step, _ = make_train_step(cfg, mesh, lr=1e-3, donate=False,
                              microbatch=2)
    B, S = 4, 32
    if cfg.embed_input:
        inputs = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                                   jnp.bfloat16)
    else:
        inputs = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    p2, o2, _, m = step(params, opt, jnp.zeros(()), inputs, labels, pos)
    loss = float(m["loss"])
    assert np.isfinite(loss) and loss > 0, (arch, loss)
    # decode (batch-sharded on the 2-wide data axis)
    caches = M.init_cache(cfg, 4, 32, local=False)
    dec, _ = serve_step.make_decode_step(cfg, mesh, batch_sharded=True)
    tok = (jax.random.normal(jax.random.PRNGKey(3), (4, 1, cfg.d_model),
                             jnp.bfloat16) if cfg.embed_input
           else jnp.full((4, 1), 3, jnp.int32))
    dpos = (jnp.full((3, 4, 1), 8, jnp.int32) if cfg.rope == "mrope"
            else jnp.full((4, 1), 8, jnp.int32))
    nxt, _ = dec(p2, caches, tok, dpos, jnp.asarray(8, jnp.int32))
    assert np.all(np.asarray(nxt) >= 0), arch
    print(f"{arch}: loss={loss:.3f} decode ok", flush=True)
print("ALL_OK")
"""


@pytest.mark.slow
def test_all_archs_on_4dev_mesh():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=2400)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ALL_OK" in proc.stdout, proc.stdout[-2000:]


_NUMERIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import numpy as np, jax, jax.numpy as jnp
import repro
from repro.configs.reduced import reduced
from repro.models import model as M
from repro.train import optimizer
from repro.train.step import make_train_step

# qwen3 family with real TP(2) + FSDP(2) vs single-device: results must agree
cfg = dataclasses.replace(reduced("qwen3-4b"), tp=2, tp_shard=True,
                          n_heads=4, n_kv_heads=4, vocab_size=256)
mesh1 = jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,)*3)
mesh4 = jax.make_mesh((1, 2, 2), ("pod", "data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,)*3)
params = M.init_params(cfg, jax.random.PRNGKey(0))
B, S = 4, 32
inputs = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)

outs = []
for mesh in (mesh1, mesh4):
    step, _ = make_train_step(cfg, mesh, lr=1e-2, donate=False)
    p2, _, _, m = step(params, optimizer.init(params), jnp.zeros(()),
                       inputs, labels, pos)
    outs.append((float(m["loss"]), float(m["grad_norm"]),
                 [np.asarray(x, np.float32) for x in jax.tree.leaves(p2)]))

(l1, g1, t1), (l4, g4, t4) = outs
assert abs(l1 - l4) < 5e-3, (l1, l4)
assert abs(g1 - g4) / max(g1, 1e-9) < 5e-2, (g1, g4)
for a, b in zip(t1, t4):
    np.testing.assert_allclose(a, b, atol=5e-2)
print(f"NUMERIC_OK loss {l1:.4f}~{l4:.4f} gnorm {g1:.3f}~{g4:.3f}")
"""


@pytest.mark.slow
def test_spmd_numeric_equivalence():
    """Loss/grad-norm/updated params agree between the (1,1,1) and (1,2,2)
    meshes — validates the manual-SPMD collective algebra (FSDP gathers,
    TP psums, grad sync) end to end."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _NUMERIC_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "NUMERIC_OK" in proc.stdout, proc.stdout[-2000:]
