"""repro — production JAX framework reproducing
"A Lazy Approach for Efficient Index Learning" (Liu, Kulik, Ma, Qi; CS.DB 2021).

Layers:
  repro.core     — the paper: agile model reuse, RMI/RMRT, bounds, baselines.
  repro.kernels  — Pallas TPU kernels for the index hot paths.
  repro.models   — LM substrate (10 assigned architectures).
  repro.train    — distributed training runtime (shard_map manual SPMD).
  repro.serve    — serving runtime (paged KV cache, decode loop).
  repro.data     — data pipeline with learned-index integration.
  repro.launch   — mesh/dry-run/roofline/launcher entry points.

The index core operates on 64-bit keys (SOSD-style u64); we enable x64 here.
All LM code pins bf16/f32 dtypes explicitly so this never leaks into it.
"""
import jax

jax.config.update("jax_enable_x64", True)

__version__ = "1.0.0"
