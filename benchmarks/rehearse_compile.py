"""Compile a training cell's step for a *described* TPU v5e (no chip
attached) and print what the compiler says: bytes on each device and the
collectives it put in. Settles the per-chip batch and the dp4 layout at no
chip time. Run from the repository root, on the CPU:

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile.py \
        [--workload bert_base.pretrain_L512] [--per-chip-batch 64] [--chips 1,4]

A compile that passes is not a chip run: nothing here is a time or a rate.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import sys
from collections import Counter
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="bert_base.pretrain_L512")
    ap.add_argument("--per-chip-batch", type=int, default=0)
    ap.add_argument("--chips", default="1,4")
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from benchmarks.runners.train import assemble
    from distributed_tensorflow_tpu.data.text import bert_batch_specs
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh

    # jax sees the CPU here, and the kernel would take its interpreted
    # branch; the chip does not. Steered here, not by an option of the program.
    # (ops/__init__ exports the function under the module's name: go by sys.modules)
    importlib.import_module(
        "distributed_tensorflow_tpu.ops.flash_attention"
    )._use_interpret = lambda: False

    workload = json.loads((HERE / "workloads" / f"{args.workload}.json").read_text())
    cfg = json.loads((HERE / "configs" / f"{workload['config']}.json").read_text())
    job = workload["traffic"]
    seq_len = job["seq_len"]
    per_chip = args.per_chip_batch or job["per_chip_batch"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)

    for n in [int(c) for c in args.chips.split(",")]:
        mesh = build_mesh({"data": -1}, devices=topo.devices[:n])
        _, _, make_state, step = assemble(cfg, seq_len, mesh)
        rep = NamedSharding(mesh, P())
        state = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            jax.eval_shape(make_state, jax.random.key(0)),
        )
        gb = per_chip * n
        specs = bert_batch_specs(mesh)
        batch = {
            k: jax.ShapeDtypeStruct(
                (gb,) if k == "nsp_label" else (gb, seq_len), jnp.int32,
                sharding=NamedSharding(mesh, specs[k]),
            )
            for k in specs
        }
        # The trainer's key on a TPU (train.make_rng): rbg.
        key = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
        key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)
        compiled = step.lower(state, batch, key).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        collectives = Counter(re.findall(
            r"\s(all-reduce(?:-start)?|all-gather(?:-start)?|reduce-scatter|"
            r"all-to-all|collective-permute(?:-start)?)\(", text))
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(json.dumps({
            "workload": args.workload, "topology": args.topology, "chips": n,
            "mesh": dict(mesh.shape), "per_chip_batch": per_chip, "seq_len": seq_len,
            "arguments_bytes": ma.argument_size_in_bytes,
            "outputs_bytes": ma.output_size_in_bytes,
            "temporaries_bytes": ma.temp_size_in_bytes,
            "aliased_bytes": ma.alias_size_in_bytes,
            "live_bytes_per_device": live,
            "pallas_custom_calls": len(re.findall(r"tpu_custom_call", text)),
            "collectives": dict(collectives),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
