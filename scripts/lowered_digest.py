"""sha256 of the decode programs of the serving cells, lowered for a described
TPU v5e on the CPU (no chip):

    JAX_PLATFORMS=cpu python scripts/lowered_digest.py [--root <checkout>]
        [--keep-lines] [lm_base|phi4_mini_flash|olmo_hybrid_7b|deepseek_v2_lite ...]

One line a cell: the digest, the text's length and its ``tpu_custom_call``
count. Run it on two checkouts to tell whether a change moved a cell's
program. A Mosaic kernel's body is embedded in the text with every op's
source position (file, line, column) in its locations, so moving a line of
ops/decode_attention.py changes the digest of every program that runs a
kernel though the program is the same; by default the source positions are
left out of the locations (the scopes stay), ``--keep-lines`` keeps them.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
from pathlib import Path

ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ap.add_argument("--root", default=str(Path(__file__).resolve().parent.parent))
ap.add_argument("--keep-lines", action="store_true")
ap.add_argument("cells", nargs="*", default=[
    "lm_base", "phi4_mini_flash", "olmo_hybrid_7b", "deepseek_v2_lite",
])
args = ap.parse_args()
root = Path(args.root).resolve()
sys.path.insert(0, str(root))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from distributed_tensorflow_tpu.models import kvcache  # noqa: E402
from distributed_tensorflow_tpu.ops import decode_attention  # noqa: E402
from distributed_tensorflow_tpu.serve.engine import _make_causal_decode  # noqa: E402

# held to the CPU a kernel would be interpreted: lower it as the chip would
decode_attention._use_interpret = lambda: False
if not args.keep_lines:
    from jax._src import source_info_util

    source_info_util.user_frame = lambda *a, **k: None
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
one = SingleDeviceSharding(
    topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
)


def _struct(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)


def _model(cell: str):
    """(model, its parameters' initialiser, slots, cache positions)."""
    if cell == "lm_base":
        from distributed_tensorflow_tpu.models.causal_lm import CausalLM, CausalLMConfig

        model = CausalLM(CausalLMConfig(vocab_size=40478, dtype=jnp.bfloat16))
        ids = jnp.zeros((1, 8), jnp.int32)
        return model, lambda: model.init(
            jax.random.PRNGKey(0), ids, jnp.ones((1, 8), bool)
        )["params"], 128, 384
    if cell == "phi4_mini_flash":
        from distributed_tensorflow_tpu.models.sambay import (
            SambaY, SambaYConfig, sambay_init_params,
        )

        model = SambaY(SambaYConfig(dtype=jnp.bfloat16))
        return model, lambda: sambay_init_params(model, jax.random.PRNGKey(0)), 128, 1536
    config = json.loads((root / f"benchmarks/configs/{cell}.json").read_text())
    if cell == "olmo_hybrid_7b":
        from benchmarks.runners import serve_olmo_hybrid as runner
        from distributed_tensorflow_tpu.models.olmo_hybrid import (
            OlmoHybrid, olmo_hybrid_init_params,
        )

        model = OlmoHybrid(runner.model_config(config))
        return model, lambda: olmo_hybrid_init_params(model, jax.random.PRNGKey(0)), 16, 4608
    from benchmarks.runners import serve_deepseek_v2 as runner
    from distributed_tensorflow_tpu.models.deepseek_v2 import (
        DeepseekV2, deepseek_v2_init_params,
    )

    model = DeepseekV2(runner.model_config(config))
    return model, lambda: deepseek_v2_init_params(model, jax.random.PRNGKey(0)), 128, 4608


for cell in args.cells:
    model, init, slots, cache_len = _model(cell)
    params = jax.tree.map(lambda x: _struct(x.shape, jnp.bfloat16), jax.eval_shape(init))
    layout = model.cache_layout("bfloat16")
    table = kvcache.structs(layout, (slots, cache_len), jax.tree.map(lambda _: one, layout))
    text = jax.jit(_make_causal_decode(model, cache_len), donate_argnums=(1, 2, 3)).lower(
        params, table, _struct((slots,), jnp.int32), _struct((4, slots), jnp.int32)
    ).as_text()
    print(cell, hashlib.sha256(text.encode()).hexdigest(), len(text),
          text.count("tpu_custom_call"), flush=True)
