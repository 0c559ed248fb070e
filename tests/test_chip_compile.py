"""The flash-attention kernels compile for a TPU v5e at production shapes,
and the decode step compiles there without moving its slot table.

Interpret mode, which every other kernel test runs in, accepts what the
chip's compiler refuses: a slice off the (8, 128) tiling, more scoped VMEM
than a kernel may take. The TPU compiler is installed here and compiles for
a chip that is described, not attached — so these tests cost no chip time
and guard the kernels of the main path on every PR. A compile that passes is
not a chip run: results and times come from chip_smoke.py.

One file, on purpose: only one process at a time may load the TPU library,
and an xdist worker keeps it until it exits. The topology is described
inside a fixture, never at import, so every worker collects the same tests
and only the one that runs this file loads the library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_tensorflow_tpu.ops.flash_attention import (
    _DEFAULT_BLOCK_K,
    _DEFAULT_BLOCK_Q,
    _flat_auto,
    flash_attention,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    had = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — whatever keeps libtpu out
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        if had is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep it out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_fwd_bwd(one_chip, shape, dtype, packing):
    """Compile forward + all three gradients of the kernel, compiled mode."""

    def loss(q, k, v, mask):
        o = flash_attention(q, k, v, mask, interpret=False, packing=packing)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def run(q, k, v, mask):
        o = flash_attention(q, k, v, mask, interpret=False, packing=packing)
        return o, jax.grad(loss, argnums=(0, 1, 2))(q, k, v, mask)

    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    mask = jax.ShapeDtypeStruct(shape[:2], jnp.bool_, sharding=one_chip)
    return jax.jit(run).lower(qkv, qkv, qkv, mask).compile()


# (B, L, H, D), dtype, packing asked for, packing the auto rule must pick.
CASES = {
    # The r5 production geometry: what chip_smoke.py's kernel phase runs.
    "flat-bf16-L512": ((24, 512, 12, 64), "bfloat16", "flat", "flat"),
    "bh-bf16-L512": ((24, 512, 12, 64), "bfloat16", "bh", "flat"),
    # Past the packed path's VMEM budget: the auto rule must leave it, and
    # the q/k loops run over 512-blocks with whole-sequence K/V resident.
    "auto-bf16-L2048": ((4, 2048, 12, 64), "bfloat16", None, "bh"),
    # f32 K/V streams are twice the bf16 residency (ADVICE.md, VMEM estimate).
    "auto-f32-L1024": ((4, 1024, 12, 64), "float32", None, "bh"),
    # The largest geometries the auto rule still sends to the packed path:
    # the estimate says they fit, the compiler has to agree.
    "auto-bf16-L1024": ((2, 1024, 12, 64), "bfloat16", None, "flat"),
    "auto-f32-L512": ((2, 512, 12, 64), "float32", None, "flat"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_flash_attention_compiles_for_v5e(one_chip, case):
    shape, dtype, packing, auto = CASES[case]
    dtype = jnp.dtype(dtype)
    _, l, h, d = shape
    picked = _flat_auto(
        h, d, min(_DEFAULT_BLOCK_Q, l), min(_DEFAULT_BLOCK_K, l),
        False, l, dtype.itemsize,
    )
    assert ("flat" if picked else "bh") == auto
    compiled = _compile_fwd_bwd(one_chip, shape, dtype, packing)
    # Forward, dQ and dK/dV kernels, each a Mosaic custom call.
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("packing", ["flat", "bh"])
def test_flash_custom_calls_keep_their_name_and_carry_their_scope(
    one_chip, packing
):
    """benchmarks/layer_metrics/kernel.flash_ms selects the three kernels
    by the instruction name the TPU compiler gives them (``%attention.N``,
    after the innermost scope); kernel.flash_{fwd,dq,dkv}_ms tell them
    apart by the scope in ``op_name``. Both must hold in the compiled
    program, for both kernel families."""
    import re

    compiled = _compile_fwd_bwd(one_chip, (4, 512, 12, 64), jnp.bfloat16, packing)
    calls = [
        line.strip() for line in compiled.as_text().splitlines()
        if "tpu_custom_call" in line and " custom-call(" in line
    ]
    old = re.compile(r"^%attention[.0-9]* = .* custom-call\(")
    assert all(old.search(c) for c in calls), calls
    scopes = sorted(
        re.search(
            r'op_name="[^"]*[/(](flash_\w+?)\)*/attention/pallas_call"', c
        ).group(1)
        for c in calls
    )
    # _compile_fwd_bwd runs the forward twice: once alone, once under grad
    # (where jax writes the outermost scope as jvp(flash_fwd))
    assert sorted(set(scopes)) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert scopes.count("flash_dq") == scopes.count("flash_dkv") == 1


def test_explicit_flat_past_the_vmem_budget_is_refused_before_the_compiler(
    one_chip,
):
    with pytest.raises(ValueError, match="VMEM"):
        _compile_fwd_bwd(one_chip, (4, 2048, 12, 64), jnp.bfloat16, "flat")


# ------------------------------------------- the decode step's slot table

# benchmarks/workloads/lm_base.chat_steady: lm_base at GPT-1's sizes, 128
# slots, cache 384 (bucket 256 + 128 new tokens).
_SLOTS, _CACHE_LEN = 128, 384


def _compile_decode(one_chip, kv):
    from distributed_tensorflow_tpu.models import kvcache
    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.serve.engine import _make_causal_decode

    cfg = CausalLMConfig(vocab_size=40478, dtype=jnp.bfloat16)
    model = CausalLM(cfg)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: struct(x.shape, jnp.bfloat16),
        jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool),
            )["params"]
        ),
    )
    table = jax.tree.map(
        lambda leaf: struct(
            (cfg.num_layers, _SLOTS, _CACHE_LEN, *leaf.shape), leaf.dtype
        ),
        kvcache.cache_layout(cfg, kv),
    )
    compiled = (
        jax.jit(
            _make_causal_decode(model, _CACHE_LEN), donate_argnums=(1, 2, 3)
        )
        .lower(
            params, table,
            struct((_SLOTS,), jnp.int32), struct((4, _SLOTS), jnp.int32),
        )
        .compile()
    )
    cache_bytes = sum(
        x.dtype.itemsize * int(np.prod(x.shape))
        for x in jax.tree.leaves(table)
    )
    return compiled, (cfg.num_layers, cfg.hidden_size), cache_bytes


def _made_by(compiled, shape):
    """The entry computation's instructions whose result holds an array of
    ``shape`` (a regex over the dims), by opcode."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    holds = re.compile(r"\w+\[%s\]" % shape)
    made_by = {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z\-]*)\(", line)
        if m and holds.search(m.group(2)):
            made_by.setdefault(m.group(3), []).append(m.group(1))
    return made_by


@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
def test_decode_step_never_moves_its_slot_table(one_chip, kv, monkeypatch):
    """A cached position is one contiguous ``heads * head_dim`` row, the
    table's default layout is the one it lives in, and the step writes its
    ``layers x slots`` rows as rows of the flat table, in place
    (models/kvcache.py, "How decode_step writes and reads"). Nothing of a
    table's size is made for a leaf that has the row: not by a copy, slice,
    concatenate or convert, and not by a fusion — the select PR 28 wrote
    with was two, a pass over 3.6 GB a step (PERF.md, PR 33). This is the
    guard that keeps it so when someone touches the layer loop or the
    attention's contractions. Since PR 38 the bfloat16 table's twelve reads
    are the new-row kernel of ops/decode_attention.py, custom calls over the
    stacked leaves where they lie; int8 K/V keeps the mask form's fusions. A
    compile, not a time."""
    from distributed_tensorflow_tpu.ops import decode_attention

    # held to the CPU the kernel would be interpreted: compile it as the
    # chip would
    monkeypatch.setattr(decode_attention, "_use_interpret", lambda: False)
    compiled, (layers, row), cache_bytes = _compile_decode(one_chip, kv)
    stacked = r"bf16\[%d,%d,%d,%d\]" % (layers, _SLOTS, _CACHE_LEN, row)
    kernels = re.findall(
        r"^\s*%%(row_attention[.\d]*) = f32\[%d,16,128\]\S* custom-call\("
        r"(?=.*%s.*%s)" % (_SLOTS, stacked, stacked),
        compiled.as_text(), re.M,
    )
    assert len(kernels) == (layers if kv == "bfloat16" else 0), kernels
    # one layer's pages or all layers', whatever the element type and layout
    table = _made_by(
        compiled, r"(?:%d,|1,)?%d,%d,%d" % (layers, _SLOTS, _CACHE_LEN, row)
    )
    assert set(table) <= {
        "parameter", "get-tuple-element", "tuple", "bitcast",
    }, table
    # the same bytes as rows: only the two scatters that alias their operand
    flat = _made_by(compiled, r"%d,%d" % (layers * _SLOTS * _CACHE_LEN, row))
    assert set(flat) <= {"bitcast", "fusion"}, flat
    assert len(flat.get("fusion", ())) == 2, flat
    for name in flat["fusion"]:
        (line,) = re.findall(
            r"^\s*%%%s = .*$" % re.escape(name), compiled.as_text(), re.M
        )
        assert "kv_write/scatter" in line and "kind=kCustom" in line, line
        assert '"aliasing_operands":{"lists":[{"indices":["0"' in line, line
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 0.2e9, ma.temp_size_in_bytes  # was 3.546e9
    assert ma.alias_size_in_bytes >= cache_bytes  # both sides, every leaf


@pytest.mark.parametrize("spelling", ["split_lanes", "merged_row"])
def test_reading_a_merged_row_per_head_copies_the_layer(one_chip, spelling):
    """The finding that chose the read (PERF.md, PR 33): with the heads
    merged in the table, scores written per head — split the lane axis,
    then PR 28's ``shd,slhd->shl`` — make the compiler copy and convert the
    layer it reads, 0.2 GB of scratch a layer and more; contracted over the
    merged row against a block-diagonal query, as ``kvcache._attend`` does,
    the layer is read where it lies."""
    from distributed_tensorflow_tpu.models.kvcache import cached_attention

    heads, dim = 12, 64

    def split_lanes(q, table, position):
        k = table["k"][3].reshape(_SLOTS, _CACHE_LEN, heads, dim)
        s = jnp.einsum(
            "shd,slhd->shl", q, k, preferred_element_type=jnp.float32
        )
        seen = jnp.arange(_CACHE_LEN) <= position[:, None]
        return jnp.where(seen[:, None], s, -1e30)

    def merged_row(q, table, position):
        return cached_attention(
            q, jax.tree.map(lambda t: t[3], table), position
        )

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = struct((12, _SLOTS, _CACHE_LEN, heads * dim), jnp.bfloat16)
    compiled = (
        jax.jit({"split_lanes": split_lanes, "merged_row": merged_row}[spelling])
        .lower(
            struct((_SLOTS, heads, dim), jnp.bfloat16),
            {"k": pages, "v": pages}, struct((_SLOTS,), jnp.int32),
        )
        .compile()
    )
    scratch = compiled.memory_analysis().temp_size_in_bytes
    if spelling == "split_lanes":
        assert scratch >= 0.2e9, scratch
    else:
        assert scratch < 8e6, scratch
        layer = r"%d,%d,%d" % (_SLOTS, _CACHE_LEN, heads * dim)
        assert not _made_by(compiled, layer)


# ------------------------- a hybrid model's three cache groups, in place

def test_hybrid_decode_step_updates_all_three_groups_in_place(
    one_chip, monkeypatch
):
    """benchmarks/workloads/phi4_mini_flash.reason_steady: SambaY at
    Phi-4-mini-flash-reasoning's sizes (3.85 B parameters in bf16), 128 slots,
    cache 1,536. The donated cache — Mamba state, window rings, the one full
    K/V table, 4.10 GB — is aliased to the step's output, every leaf, and the
    program's scratch stays under half a gigabyte: the scan state is read
    and rewritten as one chain (reading the step's input while writing its
    output copied the 0.38 GB table: 0.498 GB of scratch, PERF.md PR 35),
    the rings' rows are the flat scatter of PR 33, and no reader of the full
    table makes a copy of it: its eight readers are the length-aware kernel
    of ops/decode_attention.py (PR 36), each a custom call that takes the
    table where it lies, so nothing of the table's size is made by a fusion,
    copy or convert. A compile, not a time (about 20 s)."""
    from distributed_tensorflow_tpu.models import kvcache
    from distributed_tensorflow_tpu.models.sambay import (
        SambaY,
        SambaYConfig,
        sambay_init_params,
    )
    from distributed_tensorflow_tpu.serve.engine import _make_causal_decode

    from distributed_tensorflow_tpu.ops import decode_attention

    # the process is held to the CPU, where the kernel would be interpreted:
    # compile it as the chip would
    monkeypatch.setattr(decode_attention, "_use_interpret", lambda: False)
    slots, cache_len = 128, 1536
    model = SambaY(SambaYConfig(dtype=jnp.bfloat16))

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: struct(x.shape, jnp.bfloat16),
        jax.eval_shape(
            lambda: sambay_init_params(model, jax.random.PRNGKey(0))
        ),
    )
    layout = model.cache_layout("bfloat16")
    table = kvcache.structs(
        layout, (slots, cache_len), jax.tree.map(lambda _: one_chip, layout)
    )
    compiled = (
        jax.jit(
            _make_causal_decode(model, cache_len), donate_argnums=(1, 2, 3)
        )
        .lower(
            params, table,
            struct((slots,), jnp.int32), struct((4, slots), jnp.int32),
        )
        .compile()
    )
    by_group = {
        name: nbytes
        for name, (nbytes, _) in kvcache.components(
            layout, (slots, cache_len)
        ).items()
    }
    assert by_group == {
        "cache.state": 412_876_800, "cache.window": 2_684_354_560,
        "cache.full": 1_006_632_960,
    }
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= sum(by_group.values())
    assert ma.temp_size_in_bytes < 0.5e9, ma.temp_size_in_bytes
    # the five leaves, ``last`` and the step's inputs, each an output that
    # aliases its own parameter
    text = compiled.as_text()
    aliased = re.search(r"input_output_alias=\{([^\n]*)\}, entry", text)
    assert aliased and aliased.group(1).count("may-alias") \
        + aliased.group(1).count("must-alias") >= 7, aliased
    # the table's eight readers, and nothing else that holds a table
    kernels = re.findall(
        r"^\s*%(table_attention[.\d]*) = f32\[128,20,128\]\S* custom-call\(",
        text, re.M,
    )
    assert len(kernels) == 8, kernels
    made = _made_by(compiled, r"(?:1,)?%d,%d,1280" % (slots, cache_len))
    assert set(made) <= {
        "parameter", "get-tuple-element", "tuple", "bitcast",
    }, made


# ------------- a matrix state and four K/V tables, a step and a prompt chunk

def test_delta_rule_hybrid_step_and_chunk_fit_the_chip(one_chip, monkeypatch):
    """benchmarks/workloads/olmo_hybrid_7b.longdoc_steady: Olmo-Hybrid-7B's
    first 16 layers at the published widths (4.10 B parameters in bf16), 16
    slots, cache 4,608. The decode step aliases the whole donated cache —
    twelve layers' float32 matrix state and conv tails, four K/V tables,
    5.1 GB as the chip pads it — and reserves tens of megabytes: the state is
    read and rewritten as one chain, the tables take the flat row scatter and
    no reader copies one: each full layer's read is the new-row kernel of
    ops/decode_attention.py (PR 38), a custom call whose operands are the
    stacked leaves where they lie and the step's rows. The chunk program (one row of 512 positions, no
    prefix pool, so no gather prologue) holds a row's slot twice over and a
    quarter of the chunk's scores at a time: 1.15 GB of scratch, which with
    13.3 GB of operands the chip has. Two rows reserve 2.7 GB and more,
    which it has not: the cell's ``max_batch`` is 1 for that. A
    compile, not a time (about 30 s)."""
    import json
    from pathlib import Path

    from benchmarks.runners import serve_olmo_hybrid as runner
    from distributed_tensorflow_tpu.models import kvcache
    from distributed_tensorflow_tpu.models.olmo_hybrid import (
        OlmoHybrid,
        olmo_hybrid_init_params,
    )
    from distributed_tensorflow_tpu.ops import decode_attention
    from distributed_tensorflow_tpu.serve.engine import (
        _make_causal_chunk_prefill,
        _make_causal_decode,
    )

    # held to the CPU the kernel would be interpreted: compile it as the
    # chip would
    monkeypatch.setattr(decode_attention, "_use_interpret", lambda: False)
    config = json.loads((
        Path(__file__).resolve().parents[1]
        / "benchmarks/configs/olmo_hybrid_7b.json"
    ).read_text())
    slots, cache_len, chunk = 16, 4608, config["serving"]["prefill_chunk"]
    model = OlmoHybrid(runner.model_config(config))

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: struct(x.shape, jnp.bfloat16),
        jax.eval_shape(
            lambda: olmo_hybrid_init_params(model, jax.random.PRNGKey(0))
        ),
    )
    layout = model.cache_layout("bfloat16")
    on_chip = jax.tree.map(lambda _: one_chip, layout)
    table = kvcache.structs(layout, (slots, cache_len), on_chip)
    i32 = lambda *shape: struct(shape, jnp.int32)  # noqa: E731
    step = (
        jax.jit(
            _make_causal_decode(model, cache_len), donate_argnums=(1, 2, 3)
        )
        .lower(params, table, i32(slots), i32(4, slots))
        .compile()
    )
    held = sum(
        nbytes for nbytes, _ in kvcache.components(
            layout, (slots, cache_len)
        ).values()
    )
    assert held == 4_967_792_640
    ma = step.memory_analysis()
    assert ma.alias_size_in_bytes >= held
    assert ma.temp_size_in_bytes < 64e6, ma.temp_size_in_bytes
    made = _made_by(step, r"(?:4,)?%d,%d,3840" % (slots, cache_len))
    assert set(made) <= {
        "parameter", "get-tuple-element", "tuple", "bitcast",
    }, made
    # the four full layers' reads, each over both stacked leaves whole
    kernels = re.findall(
        r"^\s*%(row_attention[.\d]*) = f32\[16,32,128\]\S* custom-call\("
        r"(?=.*bf16\[4,16,4608,3840\].*bf16\[4,16,4608,3840\])",
        step.as_text(), re.M,
    )
    assert len(kernels) == 4, kernels

    rows = 1
    prompt_chunk = (
        jax.jit(
            _make_causal_chunk_prefill(model, cache_len, 16, pooled=False),
            donate_argnums=(1, 2),
        )
        .lower(
            params, table, i32(slots),
            kvcache.structs(layout, (1, 16), on_chip), i32(rows, chunk),
            i32(rows), i32(rows), i32(rows, 256), i32(rows), i32(rows),
            struct((rows,), jnp.float32), i32(rows),
        )
        .compile()
    )
    ma = prompt_chunk.memory_analysis()
    assert ma.alias_size_in_bytes >= held
    assert ma.temp_size_in_bytes < 1.3e9, ma.temp_size_in_bytes


# ---------- a latent row a position and 64 routed experts, a step and a chunk

def test_latent_attention_step_and_chunk_fit_the_chip(one_chip, monkeypatch):
    """benchmarks/workloads/deepseek_v2_lite.docqa_steady: DeepSeek-V2-Lite's
    first 7 layers at the published widths (4.01 B parameters in bf16), 128
    slots, cache 4,608. The decode step aliases the donated latent table —
    7 layers of one row a position, 576 lanes held as 640, 5.28 GB — and
    makes nothing of its size: each layer's absorbed read is the latent
    kernel of ops/decode_attention.py, a custom call whose operands are the
    stacked table where it lies and the step's row, and the rows go in as
    rows of the flat table (a 576-lane row could not: the compiler copies
    the whole table to write one, models/deepseek_v2.py::row_width). The routed
    experts are grouped matmuls whose FLOPs are the rows' own, not every row
    through every expert. The chunk program (one row of 512) reserves a
    quarter of a gigabyte; two rows reserve 3 GB, past what the chip has
    beside 13.3 GB of operands: the cell's ``max_batch`` is 1. A compile,
    not a time (about 30 s)."""
    import json
    from pathlib import Path

    from benchmarks.runners import serve_deepseek_v2 as runner
    from distributed_tensorflow_tpu.models import kvcache
    from distributed_tensorflow_tpu.models.deepseek_v2 import (
        DeepseekV2,
        deepseek_v2_init_params,
    )
    from distributed_tensorflow_tpu.ops import decode_attention
    from distributed_tensorflow_tpu.serve.engine import (
        _make_causal_chunk_prefill,
        _make_causal_decode,
    )

    # held to the CPU the kernel would be interpreted: compile it as the
    # chip would
    monkeypatch.setattr(decode_attention, "_use_interpret", lambda: False)
    config = json.loads((
        Path(__file__).resolve().parents[1]
        / "benchmarks/configs/deepseek_v2_lite.json"
    ).read_text())
    slots, cache_len, chunk = 128, 4608, config["serving"]["prefill_chunk"]
    model = DeepseekV2(runner.model_config(config))

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: struct(x.shape, jnp.bfloat16),
        jax.eval_shape(
            lambda: deepseek_v2_init_params(model, jax.random.PRNGKey(0))
        ),
    )
    layout = model.cache_layout("bfloat16")
    on_chip = jax.tree.map(lambda _: one_chip, layout)
    table = kvcache.structs(layout, (slots, cache_len), on_chip)
    i32 = lambda *shape: struct(shape, jnp.int32)  # noqa: E731
    step = (
        jax.jit(
            _make_causal_decode(model, cache_len), donate_argnums=(1, 2, 3)
        )
        .lower(params, table, i32(slots), i32(4, slots))
        .compile()
    )
    held = kvcache.components(layout, (slots, cache_len))["cache.latent"][0]
    assert held == 7 * slots * cache_len * 640 * 2 == 5_284_823_040
    ma = step.memory_analysis()
    assert ma.alias_size_in_bytes >= held
    assert ma.temp_size_in_bytes < 200e6, ma.temp_size_in_bytes
    made = _made_by(step, r"(?:7,)?%d,%d,640" % (slots, cache_len))
    assert set(made) <= {
        "parameter", "get-tuple-element", "tuple", "bitcast",
    }, made
    # the seven layers' reads, each over the stacked table whole
    kernels = re.findall(
        r"^\s*%%(latent_row_attention[.\d]*) = f32\[%d,16,640\]\S* "
        r"custom-call\((?=.*bf16\[7,%d,%d,640\])" % (slots, slots, cache_len),
        step.as_text(), re.M,
    )
    assert len(kernels) == 7, kernels
    # 6 MoE layers x 768 rows x (2,048 x 2,816 + 1,408 x 2,048) x 2: the
    # rows' own experts; every row through all 64 would be 64 times that
    routed = 6 * 768 * 3 * 2048 * 1408 * 2
    cost = step.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert routed < cost["flops"] < 8 * routed, cost["flops"]

    rows = 1
    prompt_chunk = (
        jax.jit(
            _make_causal_chunk_prefill(model, cache_len, 16, pooled=False),
            donate_argnums=(1, 2),
        )
        .lower(
            params, table, i32(slots),
            kvcache.structs(layout, (1, 16), on_chip), i32(rows, chunk),
            i32(rows), i32(rows), i32(rows, 256), i32(rows), i32(rows),
            struct((rows,), jnp.float32), i32(rows),
        )
        .compile()
    )
    ma = prompt_chunk.memory_analysis()
    assert ma.alias_size_in_bytes >= held
    assert ma.temp_size_in_bytes < 400e6, ma.temp_size_in_bytes


# -------------------------------------- the MLM head over the masked rows

def test_mlm_head_gathers_its_rows_at_the_cell_shape(one_chip):
    """benchmarks/workloads/bert_base.pretrain_L512: 64 x 512 positions of
    BERT-base in bf16, masked at 15%. The head's value and gradients compile
    as one conditional whose gathered branch runs the tied decoder over 5,504
    rows and pays nothing for the dense one: differentiating through the
    branch instead would have it write the dense branch's residuals, the
    [32768, 30522] logits, as zeros. A compile, not a time."""
    import re

    from distributed_tensorflow_tpu.models.bert import (
        BertConfig,
        BertForPreTraining,
        _mlm_head_stats,
        mlm_gather_rows,
    )

    rows, vocab = 64 * 512, 30522
    k_rows = mlm_gather_rows(rows, 0.15)
    assert k_rows == 5504
    model = BertForPreTraining(BertConfig(dtype=jnp.bfloat16, num_layers=1))
    head_stats = _mlm_head_stats(model, 0.15)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ids = jnp.zeros((1, 8), jnp.int32)
    params = jax.tree.map(
        lambda x: struct(x.shape, x.dtype),
        jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), ids, jnp.ones((1, 8), bool), ids
            )["params"]
        ),
    )

    def mlm_loss(params, hidden, targets):
        num, den, _, share = head_stats(params, hidden, targets)
        return num / jnp.maximum(den, 1.0), share

    compiled = (
        jax.jit(jax.value_and_grad(mlm_loss, argnums=(0, 1), has_aux=True))
        .lower(
            params, struct((64, 512, 768), jnp.bfloat16),
            struct((64, 512), jnp.int32),
        )
        .compile()
    )
    text = compiled.as_text()
    (branches,) = re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}", text)
    dense, gathered = (
        # a computation's text: from its header to the closing brace
        text[text.index(f"\n{name.strip()} ("):].split("\n}\n", 1)[0]
        for name in branches.split(",")
    )

    def results(body, dims):  # instructions whose result has these dims
        return re.findall(
            r"= \(?\w+\[%s\]\S* ([a-z][a-z\-]*)\(" % dims, body
        )

    assert results(dense, f"{rows},{vocab}")
    assert results(gathered, f"{k_rows},{vocab}")  # the decoder's 5,504 rows
    # nothing of the dense head's size in the gathered branch, zeros or other
    assert not results(gathered, rf"(?:\d+,)*{rows},{vocab}")
    assert not results(gathered, rf"64,512,{vocab}")
    assert "broadcast" not in results(gathered, rf"(?:\d+,)+{vocab}")
