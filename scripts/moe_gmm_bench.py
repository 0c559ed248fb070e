"""Times the routed experts' grouped matmuls on the chip two ways, at
DeepSeek-V2-Lite's widths (64 experts, hidden 2,048, expert FFN 1,408): XLA's
``jax.lax.ragged_dot`` (what ``parallel/moe.py::moe_dropless`` runs) against
the Pallas kernel ``jax.experimental.pallas.ops.tpu.megablox.gmm``, each as
one expert FFN (gate | up, SiLU, down) over rows sorted by expert. Two shapes:
a decode step's rows (128 slots x 6 choices = 768) and a chunk's (512 x 6 =
3,072), the rows of tokens routed top-6 by a random softmax router. One JSON
line a shape: median milliseconds of each over ``--iters`` calls, the
compiled program's FLOPs by XLA's cost analysis (against the rows' own and
against every row through every expert), and the largest difference between
the two results.

    python scripts/moe_gmm_bench.py [--iters 50]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    e, d, f, k = 64, 2048, 1408, 6
    key = jax.random.key(0)
    w_gu = (jax.random.normal(key, (e, d, 2 * f)) * 0.02).astype(jnp.bfloat16)
    w_d = (jax.random.normal(key, (e, f, d)) * 0.02).astype(jnp.bfloat16)

    def ffn(mm):
        # the weights are operands: closed over, they would be constants of
        # the program (2.6 GB of executable)
        def run(x, w_gu, w_d, sizes):
            g, u = jnp.split(mm(x, w_gu, sizes), 2, axis=-1)
            return mm((jax.nn.silu(g) * u).astype(x.dtype), w_d, sizes)
        return jax.jit(run)

    forms = {
        "ragged_dot": ffn(lambda x, w, s: jax.lax.ragged_dot(
            x, w, s, preferred_element_type=jnp.float32)),
        "megablox_gmm": ffn(lambda x, w, s: gmm(
            x, w, s, preferred_element_type=jnp.float32)),
    }
    rng = np.random.default_rng(0)
    for tokens in (128, 512):
        rows = tokens * k
        logits = rng.normal(size=(tokens, e))
        choice = np.argsort(-logits, axis=1)[:, :k].ravel()
        sizes = jnp.asarray(np.bincount(choice, minlength=e), jnp.int32)
        x = jnp.asarray(rng.normal(size=(rows, d)), jnp.bfloat16)
        out = {"rows": rows, "device": jax.devices()[0].device_kind,
               "flops_own_rows": 3 * 2 * rows * d * f,
               "flops_every_row_every_expert": 3 * 2 * rows * d * f * e}
        results = {}
        for name, fn in forms.items():
            cost = fn.lower(x, w_gu, w_d, sizes).compile().cost_analysis()
            cost = cost[0] if isinstance(cost, list) else cost
            results[name] = jax.block_until_ready(fn(x, w_gu, w_d, sizes))
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, w_gu, w_d, sizes))
                times.append(time.perf_counter() - t0)
            out[name] = {"median_ms": 1e3 * float(np.median(times)),
                         "flops": cost.get("flops")}
        out["max_abs_difference"] = float(jnp.max(jnp.abs(
            results["ragged_dot"] - results["megablox_gmm"])))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
