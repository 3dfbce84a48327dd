"""Is the device's float32 division exact on c / c, and what impurity does a
pure node read? (A v5e: 50,261 of the counts 1 ... 200,000 are off by an ulp,
gini +-2.4e-7 — PERF.md section 6, PR 38; a CPU: none.) From the root of a
checkout: ``PYTHONPATH=. python3 scripts/tpu_division_probe.py``."""
import numpy as np, jax, jax.numpy as jnp
from spark_rapids_ml_tpu.ops import tree_kernels as tk
c = jnp.arange(1, 200001, dtype=jnp.float32)
q = np.asarray(jax.jit(lambda c: c / jnp.maximum(c, 1e-12))(c))
bad = np.flatnonzero(q != 1.0)
print("device", jax.devices()[0].device_kind, "c/c != 1 for", len(bad), "of", len(q), "counts; first", (bad[:8] + 1).tolist(), "values", q[bad[:4]].tolist())
stats = jnp.stack([c, jnp.zeros_like(c)], axis=1)
g = np.asarray(jax.jit(lambda s: tk._impurity(s, "gini"))(stats))
print("gini of a pure node: nonzero for", int((g != 0).sum()), "max", float(g.max()), "min", float(g.min()))
e = np.asarray(jax.jit(lambda s: tk._impurity(s, "entropy"))(stats))
print("entropy of a pure node: nonzero for", int((e != 0).sum()), "max", float(e.max()))
