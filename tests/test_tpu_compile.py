"""Ahead-of-time compiles of the main path for a *described* TPU v5e.

The TPU's compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached (on-chip-measurement guide §2.3).  That
shows what ``interpret=True`` cannot: a Pallas kernel whose row DMA is not
aligned to the 128-lane tiling is refused here exactly as on the chip, and
a program that does not fit 16 GB of HBM is refused too.  Nothing runs, so
these cases say nothing about results or times — ``chip_smoke.py`` on the
chip does that.  Code that asks ``jax.default_backend()`` still sees the
CPU, so each case compiles the kernel or the jitted step itself.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from dmlc_core_tpu.models import (FactorizationMachine, make_train_step,
                                  make_train_step_fused, param_shardings)
from dmlc_core_tpu.ops import ragged_csr
from dmlc_core_tpu.pipeline.device_loader import (_fused_words_meta,
                                                  make_decoder)

HBM_BYTES = 16 * 10 ** 9           # one v5e chip
ROWS, NNZ, F, D = 4096, 131072, 1 << 20, 32      # the smoke's shapes
V2_META = 98304                    # v2 wire: a mid nnz bucket, raw ids
V3_META = 98304 | (20 << 32)       # compact wire: 20-bit ids, raw f32 vals
# the benchmark's CTR cells: 24-bit ids, 10-bit dictionary codes
CTR_META = 163840 | (24 << 32) | (10 << 40)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _hbm(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _names_scopes(compiled, scopes):
    """The TPU compiler keeps every ``jax.named_scope`` of the program in
    some instruction's ``op_name`` (a fusion reads its root's), so a device
    trace is read by these names and not by ``fusion.N``."""
    import re
    op_names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    for scope in scopes:
        at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)")
        assert any(at.search(n) for n in op_names), (scope, sorted(op_names))


def _batch(rows=ROWS, nnz=NNZ):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return {"ids": S((nnz,), i32), "vals": S((nnz,), f32),
            "segments": S((nnz,), i32), "labels": S((rows,), f32),
            "weights": S((rows,), f32)}


def _fm_state(model, opt):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return params, jax.eval_shape(opt.init, params)


def _gather(one, width, fm):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    args = _on(one, (S((NNZ,), i32), S((NNZ,), i32), S((NNZ,), f32),
                     S((), i32), S((F, width), f32)))
    return ragged_csr._gather_pallas.lower(
        *args, num_rows=ROWS, fm=fm, interpret=False)


def case_kernel(lower):
    def run(topo):
        one = SingleDeviceSharding(topo.devices[0])
        # width 128: the one the engine rule sends to Pallas on a TPU
        compiled = lower(one, 128).compile()
        assert "tpu_custom_call" in compiled.as_text()
    return run


def case_refused_width(width):
    def run(topo):
        one = SingleDeviceSharding(topo.devices[0])
        # the kernel was not repaired for the widths the rule sends to
        # XLA: pinning Pallas there hands it to Mosaic, whose reason
        # reaches the caller
        assert not ragged_csr.mosaic_row_dma_ok(width)
        with pytest.raises(Exception, match="aligned to tiling"):
            _gather(one, width, False).compile()
    return run


def case_decoder(meta, gathers):
    def run(topo):
        one = SingleDeviceSharding(topo.devices[0])
        buf = jax.ShapeDtypeStruct((_fused_words_meta(ROWS, meta),),
                                   jnp.int32, sharding=one)
        # the program _get_unpack builds off-CPU: donated wire buffer
        compiled = jax.jit(make_decoder(ROWS, meta),
                           donate_argnums=(0,)).lower(buf).compile()
        assert _hbm(compiled) < HBM_BYTES
        text = compiled.as_text()
        assert "tpu_custom_call" not in text
        assert "HloModule jit__unpack" in text
        _names_scopes(compiled, ["wire_decode/ids", "wire_decode/vals",
                                 "wire_decode/segments"])
        # segments come from a scatter and a prefix sum: a search over
        # row_ptr would be a loop of scalar gathers (15 ms a batch on a v5e)
        assert " while(" not in text
        # bit-packed streams unpack with shifts the trace fixes; the one
        # gather a program may hold is the value dictionary's, whose
        # indices are data.  Word indices computed from an iota are two
        # scalar gathers a stream (1.16 ms each on a v5e)
        assert text.count(" gather(") == gathers
    return run


def _table_shaped(text, f):
    """Copies, transposes or broadcasts of an ``f``-row table in ``text``."""
    import re
    return re.findall(rf"= f32\[(?:{f},{D}|{D},{f})\]\S* "
                      r"(?:copy|transpose|broadcast)\(", text)


def case_train_step(kstep, f=F, rows=ROWS, nnz=NNZ):
    def run(topo):
        from dmlc_core_tpu.utils.metrics import metrics
        one = SingleDeviceSharding(topo.devices[0])
        model = FactorizationMachine(num_features=f, dim=D)
        opt = optax.adam(1e-2)
        params, opt_state = _on(one, _fm_state(model, opt))
        if kstep == 1:
            lowered = make_train_step(model, opt).lower(
                params, opt_state, _on(one, _batch(rows, nnz)))
        else:
            bufs = jax.ShapeDtypeStruct(
                (kstep, _fused_words_meta(rows, V3_META)), jnp.int32,
                sharding=one)
            lowered = make_train_step_fused(
                model, opt, rows=rows, meta=V3_META, k=kstep).lower(
                    params, opt_state, bufs)
        # arguments on a TPU: both tables take the fused update's kernel
        assert metrics.gauge("train.fused_tables").value == 2
        compiled = lowered.compile()
        assert _hbm(compiled) < HBM_BYTES
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert " %table_update" in text
        # the kernel reads the tables in the layout XLA holds them in, and
        # no gradient as large as a table is built (the dense step's
        # temporaries hold one, f x 32 float32: at 2^20 rows and 131 072
        # ids the batch's own [nnz, 32] buffers, 64 MiB each with rows
        # padded to 128 lanes, outweigh it — 205 against 137 MB; the cell's
        # case below holds the temporaries to their bound)
        assert not _table_shaped(text, f)
        _names_scopes(compiled, [
            "loss_and_grad", "table_rows", "optimizer_update", "apply_updates",
            "table_update", "csr_gather", "csr_segment_sum", "loss",
            "transpose(jvp(csr_gather))"]
            + (["wire_decode/segments"] if kstep > 1 else []))
        return compiled
    return run


def case_train_step_cell(topo):
    """The benchmark's train cell: 2^24 x 32, batch 4096 x 163 840 — the
    dense step's 2.02 GiB of temporaries (a zero gradient table) are under
    half a GiB."""
    compiled = case_train_step(1, f=1 << 24, nnz=163840)(topo)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 ** 29 < (1 << 24) * D * 4, temp


def case_serving_bucket(ragged):
    def run(topo):
        from dmlc_core_tpu.serving import InferenceEngine
        one = SingleDeviceSharding(topo.devices[0])
        model = FactorizationMachine(num_features=F, dim=D)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        engine = InferenceEngine(model, params, postprocess="sigmoid",
                                 ragged=ragged)
        bucket = engine.ladder.buckets[-1]
        # _get_compiled's program, with the donation the chip turns on
        compiled = jax.jit(engine._forward_fn(), donate_argnums=(1,)).lower(
            _on(one, params), _on(one, engine._batch_avals(bucket))).compile()
        assert _hbm(compiled) < HBM_BYTES
        assert "tpu_custom_call" not in compiled.as_text()
    return run


def case_mesh_step(topo):
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    model = FactorizationMachine(num_features=F, dim=D)
    opt = optax.adam(1e-2)
    params, _ = _fm_state(model, opt)
    shardings = param_shardings(model, params, mesh)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                      sharding=shardings[k])
              for k, v in params.items()}
    # adam's moments follow their parameter; the step count replicates
    rep = NamedSharding(mesh, P())
    opt_state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(opt.init, params))
    opt_state = (opt_state[0]._replace(mu=params, nu=params),
                 *opt_state[1:])
    batch = _on(NamedSharding(mesh, P("dp")), _batch())
    compiled = make_train_step(model, opt, mesh).lower(
        params, opt_state, batch).compile()
    assert "all-reduce" in compiled.as_text()
    # a meshed step keeps the dense update: no kernel
    assert "tpu_custom_call" not in compiled.as_text()
    assert _hbm(compiled) < HBM_BYTES      # bytes on each device


# the document scorers' programs before the class learnt the ``mellum``
# type (per-kind rotation, softmax routing, optional block parts), by
# ``_program_digest``: those three files have to compile to the same
# programs, instruction for instruction
PROGRAMS = {"kimi_linear_48b_ep2_l5": "a589a989ec647723",
            "gigachat31_702b_ep16_l5": "e963a16bcd630264",
            "trinity_large_400b_ep8_l5": "e5ec9287337ee361"}


def _program_digest(compiled) -> str:
    """The compiled program's text without what says where it came from:
    the tables of source files and stack frames, each instruction's
    ``metadata`` and the custom calls' ``backend_config`` (a Pallas
    kernel's serialised body carries its source lines); what is left is
    every instruction, shape, layout and schedule."""
    import hashlib
    import re
    head, _, body = compiled.as_text().partition("\n")
    text = head + "\n" + body[re.search(r"^(%|ENTRY)", body, re.M).start():]
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r', backend_config=(\{.*?\}|"(?:[^"\\]|\\.)*")(?=[,\n])',
                  "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _attention_is_the_kernel(text, model, mixer="mla"):
    """Every ``mixer`` layer's attention is one ``doc_attention`` Pallas
    kernel, selected at lowering, under the scope a trace reads, and no
    loop of the ``jnp`` walk is left under that scope."""
    import re
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and " %doc_attention" in line]
    assert len(kernels) == sum(model.mixer(layer) == mixer
                               for layer in range(1, model.layers + 1))
    for line in kernels:
        assert 'custom_call_target="tpu_custom_call"' in line
        assert re.search(rf'op_name="[^"]*{mixer}/attention/', line), \
            line[:300]
    loops = [line for line in text.splitlines() if " while(" in line]
    assert loops                         # the head's and the dispatch's
    for line in loops:
        assert not re.search(rf'op_name="[^"]*{mixer}/attention', line), \
            line[-300:]


def _combine_is_the_kernel(text, model, t):
    """Every mixture layer brings its rows to their tokens by one
    ``moe_combine`` Pallas kernel, selected at lowering, under the scope a
    trace reads — a holder of part of the experts adds each block's rows
    to the ``[T, H]`` float32 sums, a holder of every expert sums each
    token's rows once after the loop; no scatter into the sums is left."""
    import re
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and " %moe_combine" in line]
    assert len(kernels) == model.layers - model.dense_layers
    for line in kernels:
        assert 'custom_call_target="tpu_custom_call"' in line
        assert re.search(r'op_name="[^"]*moe/combine/', line), line[:300]
    sums = rf"f32\[{t},(?:{model.hidden}|{model.hidden // 128},128)\]"
    assert not re.search(rf"= {sums}\S* scatter\(", text)


def case_document_scorer(topo):
    """The document scorer at the benchmark's published widths and timed
    sizes (layers 1-5, 128 of 256 experts, half the vocabulary, 16
    documents in 32 768 tokens, bfloat16): it fits the chip beside its
    8.57 GB of parameters, the experts are a grouped product and neither
    the ``[T, T]`` scores nor the ``[T, V]`` logits exist whole."""
    import re

    from dmlc_core_tpu.models.hybrid_lm import HybridMoELM, load_arch
    here = os.path.dirname(os.path.abspath(__file__))
    model = HybridMoELM(load_arch(os.path.join(
        here, "..", "benchmarks", "chip", "configs",
        "kimi_linear_48b_ep2_l5.json")))
    one = SingleDeviceSharding(topo.devices[0])
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    t, rows = 32768, 16
    params = jax.tree.map(lambda shape: S(shape, model.dtype, sharding=one),
                          model.shapes(),
                          is_leaf=lambda x: isinstance(x, tuple))
    batch = _on(one, dict(_batch(rows, t), row_ptr=S((rows + 1,), i32)))
    compiled = jax.jit(model.forward_counted).lower(params, batch).compile()
    assert sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(params)) \
        == 4_282_936_192
    assert _program_digest(compiled) == PROGRAMS["kimi_linear_48b_ep2_l5"]
    # 15.41 GB with the temporaries of the jnp chunk (PR 30); the kernel
    # keeps them in VMEM: 12.18 GB (PR 36, and with attention's kernel)
    assert _hbm(compiled) < 12_200_000_000 < 15_410_000_000 < HBM_BYTES
    text = compiled.as_text()
    assert "ragged-dot" in text                  # experts: grouped products
    # every KDA layer's chunk is the Pallas kernel, selected at lowering
    # (this process's backend is the CPU), under the scope a trace reads
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and " %kda_chunk" in line]
    assert len(kernels) == sum(model.mixer(layer) == "kda"
                               for layer in range(1, model.layers + 1))
    for line in kernels:
        assert 'custom_call_target="tpu_custom_call"' in line
        assert re.search(r'op_name="[^"]*kda/scan/', line), line[:300]
    _attention_is_the_kernel(text, model)
    # half the experts held, and no ``[T * k, H]`` buffer: the temporaries
    # are under the whole buffer's 3.61 GB (PR 36-39)
    _combine_is_the_kernel(text, model, t)
    assert compiled.memory_analysis().temp_size_in_bytes < 3_610_738_176
    top = re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]", text,
                     re.M)
    chunks = t // 64
    for dims in top:
        d = [int(x) for x in dims.split(",")]
        assert not (t in d and model.vocab in d), dims     # [T, V]
        assert d.count(t) < 2, dims                        # [T, T]
        # the chunk's [N, C, H, d] <-> [N, H, C, d] copies went with the
        # jnp formulation: the kernel cuts its tiles from [T, H * d]
        assert not (len(d) == 4 and d[0] == chunks), dims
    _names_scopes(compiled, [
        "lm_embed", "kda/conv", "kda/gates", "kda/scan", "mla/project",
        "mla/attention", "moe/router", "moe/dispatch", "moe/experts",
        "moe/shared", "moe/combine", "dense_mlp", "lm_head"])


def case_document_scorer_dsv3(topo):
    """The same class on the ``deepseek_v3``-type configuration at its timed
    sizes (one dense and four mixture layers at published widths, 16 of 256
    experts, an eighth of the vocabulary, 8 documents in 16 384 tokens,
    bfloat16): it fits the chip beside its 8.58 GB of parameters, the
    experts' rows are carried in blocks (no ``[T * k, H]`` buffer), and
    neither the ``[T, T]`` scores nor the ``[T, V]`` logits exist whole."""
    import re

    from dmlc_core_tpu.models.hybrid_lm import HybridMoELM, load_arch
    here = os.path.dirname(os.path.abspath(__file__))
    model = HybridMoELM(load_arch(os.path.join(
        here, "..", "benchmarks", "chip", "configs",
        "gigachat31_702b_ep16_l5.json")))
    one = SingleDeviceSharding(topo.devices[0])
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    t, rows = 16384, 8
    params = jax.tree.map(lambda shape: S(shape, model.dtype, sharding=one),
                          model.shapes(),
                          is_leaf=lambda x: isinstance(x, tuple))
    batch = _on(one, dict(_batch(rows, t), row_ptr=S((rows + 1,), i32)))
    compiled = jax.jit(model.forward_counted).lower(params, batch).compile()
    assert sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(params)) \
        == 4_291_256_320
    assert _program_digest(compiled) == PROGRAMS["gigachat31_702b_ep16_l5"]
    # 8.583 GB of arguments + 3.177 GB of temporaries (PR 37); 3.008 with
    # attention's kernel (PR 38)
    assert _hbm(compiled) < 11_760_000_000 < 12_000_000_000 < HBM_BYTES
    text = compiled.as_text()
    assert "ragged-dot" in text                  # experts: grouped products
    assert " %kda_chunk" not in text             # no KDA layer, no kernel
    _attention_is_the_kernel(text, model)
    _combine_is_the_kernel(text, model, t)
    top = re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]", text,
                     re.M)
    assignments = t * model.top_k
    for dims in top:
        d = [int(x) for x in dims.split(",")]
        assert not (t in d and model.vocab in d), dims     # [T, V]
        assert d.count(t) < 2, dims                        # [T, T]
        assert not (assignments in d and model.hidden in d), dims
    _names_scopes(compiled, [
        "lm_embed", "mla/q_lora", "mla/project", "mla/rope",
        "mla/attention", "mla/out", "moe/router", "moe/dispatch",
        "moe/experts", "moe/shared", "moe/combine", "dense_mlp", "lm_head"])


def case_document_scorer_afmoe(topo):
    """The same class on the ``afmoe``-type configuration at its timed
    sizes (one dense and four mixture layers at published widths, 32 of 256
    experts, an eighth of the vocabulary, 8 documents in 32 768 tokens,
    bfloat16): it fits the chip beside its 8.64 GB of parameters, every
    layer's attention is the kernel on grouped keys — no key or value array
    repeated to the 48 query heads exists — and neither the ``[T, T]``
    scores nor the ``[T, V]`` logits exist whole."""
    import re

    from dmlc_core_tpu.models.hybrid_lm import HybridMoELM, load_arch
    here = os.path.dirname(os.path.abspath(__file__))
    model = HybridMoELM(load_arch(os.path.join(
        here, "..", "benchmarks", "chip", "configs",
        "trinity_large_400b_ep8_l5.json")))
    one = SingleDeviceSharding(topo.devices[0])
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    t, rows = 32768, 8
    params = jax.tree.map(lambda shape: S(shape, model.dtype, sharding=one),
                          model.shapes(),
                          is_leaf=lambda x: isinstance(x, tuple))
    batch = _on(one, dict(_batch(rows, t), row_ptr=S((rows + 1,), i32)))
    compiled = jax.jit(model.forward_counted).lower(params, batch).compile()
    assert sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(params)) \
        == 4_321_903_872
    assert _program_digest(compiled) \
        == PROGRAMS["trinity_large_400b_ep8_l5"]
    # 8.644 GB of arguments + the temporaries (PR 39; ISSUE 39 expected
    # 12-13.5 GB in all)
    assert _hbm(compiled) < 13_500_000_000 < HBM_BYTES
    text = compiled.as_text()
    assert "ragged-dot" in text                  # experts: grouped products
    assert " %kda_chunk" not in text             # no KDA layer, no kernel
    _attention_is_the_kernel(text, model, "gqa")
    _combine_is_the_kernel(text, model, t)
    top = re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]", text,
                     re.M)
    assignments = t * model.top_k
    for dims in top:
        d = [int(x) for x in dims.split(",")]
        assert not (t in d and model.vocab in d), dims     # [T, V]
        assert d.count(t) < 2, dims                        # [T, T]
        assert not (assignments in d and model.hidden in d), dims
    # keys and values leave the projections 8 heads wide and reach the
    # kernel so, [T, 8 * 128] and [8, 128, T] beside q's [48, 128, T]
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and " %doc_attention" in line]
    for line in kernels:
        assert f"bf16[{t},{model.kv_heads * model.head_dim}]" in line
        assert f"bf16[{model.kv_heads},{model.head_dim},{t}]" in line
        assert f"bf16[{model.heads},{model.head_dim},{t}]" in line
    _names_scopes(compiled, [
        "lm_embed", "gqa/project", "gqa/qk_norm", "gqa/rope",
        "gqa/attention", "gqa/gate", "gqa/out", "post_norm", "moe/router",
        "moe/dispatch", "moe/experts", "moe/shared", "moe/combine",
        "dense_mlp", "lm_head"])


def case_document_scorer_mellum(topo):
    """The same class on the ``mellum``-type configuration at its timed
    sizes (eight layers at published widths, all 64 experts of each, the
    whole vocabulary, 8 documents in 32 768 tokens, bfloat16): it fits the
    chip beside its 7.59 GB of parameters; every layer's attention is the
    kernel on grouped keys, every mixture layer's products are the
    grouped-matmul kernel over blocks of one mean run of rows, and every
    mixture layer sums its rows by token through the combine kernel, once
    after its loop, which carries the rows and no float32 sums; the full
    layers turn under
    ``gqa/rope_yarn``, the sliding ones under
    ``gqa/rope``; no gate, QK-norm, post-norm or shared expert exists, and
    neither the ``[T, T]`` scores nor the ``[T, V]`` logits exist whole."""
    import re

    from dmlc_core_tpu.models.hybrid_lm import HybridMoELM, load_arch
    here = os.path.dirname(os.path.abspath(__file__))
    model = HybridMoELM(load_arch(os.path.join(
        here, "..", "benchmarks", "chip", "configs",
        "mellum2_12b_a2.5b_l8.json")))
    one = SingleDeviceSharding(topo.devices[0])
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    t, rows = 32768, 8
    params = jax.tree.map(lambda shape: S(shape, model.dtype, sharding=one),
                          model.shapes(),
                          is_leaf=lambda x: isinstance(x, tuple))
    batch = _on(one, dict(_batch(rows, t), row_ptr=S((rows + 1,), i32)))
    compiled = jax.jit(model.forward_counted).lower(params, batch).compile()
    assert sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(params)) \
        == 3_794_966_784
    # 7.59 GB of arguments + the temporaries (reckoned at 10.5-11.5 GB in
    # all before the first compile)
    assert _hbm(compiled) < 12_000_000_000 < HBM_BYTES
    text = compiled.as_text()
    # experts: each product the grouped-matmul kernel, under the scope a
    # trace reads, over blocks of one mean run of rows (T k / E)
    block = t * model.top_k // model.experts
    products = [line for line in text.splitlines()
                if "custom-call(" in line and " %gmm" in line]
    assert len(products) == 2 * (model.layers - model.dense_layers)
    for line in products:
        assert 'custom_call_target="tpu_custom_call"' in line
        assert re.search(r'op_name="[^"]*moe/experts/', line), line[:300]
    assert "ragged-dot" not in text
    assert f"bf16[{block},{model.hidden}]" in text      # a block's rows
    _attention_is_the_kernel(text, model, "gqa")
    _combine_is_the_kernel(text, model, t)
    # by token: the loop carries the rows in sorted order as words (nine
    # word rows of bfloat16 pairs a row), not the [T, H / 128, 128] sums
    words = model.hidden // 256
    assert f"u32[{t * model.top_k * words},128]" in text
    assert f"f32[{t},{model.hidden // 128},128]" not in text
    top = re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]", text,
                     re.M)
    for dims in top:
        d = [int(x) for x in dims.split(",")]
        assert not (t in d and model.vocab in d), dims     # [T, V]
        assert d.count(t) < 2, dims                        # [T, T]
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    for absent in ("gqa/gate", "gqa/qk_norm/", "post_norm", "moe/shared"):
        assert absent not in op_names, absent
    _names_scopes(compiled, [
        "lm_embed", "gqa/project", "gqa/rope", "gqa/rope_yarn",
        "gqa/attention", "gqa/out", "moe/router", "moe/dispatch",
        "moe/experts", "moe/combine", "lm_head"])


def case_document_scorer_sambay(topo):
    """The SambaY scorer whole at its timed sizes (32 layers at published
    widths, the whole tied vocabulary, 8 documents in 32 768 tokens,
    bfloat16): it fits the chip beside its 7.71 GB of parameters, every
    Mamba layer's scan is the ``selective_scan`` kernel and every attention
    layer's walk the ``diff_attention`` kernel, selected at lowering, under
    the scopes a trace reads; neither the ``[T, T]`` scores nor the
    ``[T, V]`` logits nor a ``[T, C, N]`` state exist whole."""
    import re

    from dmlc_core_tpu.models.sambay_lm import SambaYLM, load_arch
    here = os.path.dirname(os.path.abspath(__file__))
    model = SambaYLM(load_arch(os.path.join(
        here, "..", "benchmarks", "chip", "configs",
        "phi4_mini_flash_3.8b.json")))
    one = SingleDeviceSharding(topo.devices[0])
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    t, rows = 32768, 8
    params = jax.tree.map(lambda shape: S(shape, model.dtype, sharding=one),
                          model.shapes(),
                          is_leaf=lambda x: isinstance(x, tuple))
    batch = _on(one, dict(_batch(rows, t), row_ptr=S((rows + 1,), i32)))
    compiled = jax.jit(model.forward_counted).lower(params, batch).compile()
    assert sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(params)) \
        == 3_852_562_944
    # 7.706 GB of arguments + 3.545 GB of temporaries (PR 43; ISSUE 43
    # expected 4-5 GB of temporaries)
    assert _hbm(compiled) < 11_300_000_000 < HBM_BYTES
    text = compiled.as_text()
    for name, scope, count in (("selective_scan", "mamba/scan", 9),
                               ("diff_attention", "(dattn|cross)/attention",
                                16)):
        kernels = [line for line in text.splitlines()
                   if "custom-call(" in line and f" %{name}" in line]
        assert len(kernels) == count, name
        for line in kernels:
            assert 'custom_call_target="tpu_custom_call"' in line
            assert re.search(rf'op_name="[^"]*{scope}/', line), line[:300]
    assert " %doc_attention" not in text         # 64-wide heads: the pairs'
    # the kernels' flags fold to the counters' constants in the chip's program
    entry = text[text.index("\nENTRY"):]
    root = re.sub(r"/\*.*?\*/", "", re.search(
        r"ROOT %[\w.\-]+ = .*? tuple\(([^)]*)\)", entry).group(1)).split(",")

    def constant(name):
        line = re.search(rf"^\s+{re.escape(name)} = (.*)$", entry, re.M)[1]
        m = re.search(r" (?:constant\((\d+)\)|copy\((%[\w.\-]+)\))", line)
        return int(m[1]) if m and m[1] else constant(m[2]) if m else None

    paths, _ = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(model.forward_counted, params, batch))
    names = [jax.tree_util.keystr(p) for p, _ in paths]
    for counter, want in (("ssm.fused_layers", 9), ("dattn.fused_layers", 16)):
        assert constant(root[names.index(f"[1]['{counter}']")].strip()) \
            == want, counter
    top = re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]", text,
                     re.M)
    for dims in top:
        d = [int(x) for x in dims.split(",")]
        assert not (t in d and model.vocab in d), dims     # [T, V]
        assert d.count(t) < 2, dims                        # [T, T]
        assert not (t in d and model.d_state in d
                    and model.d_inner in d), dims          # [T, C, N]
    _names_scopes(compiled, [
        "lm_embed", "layer_norm", "mamba/in_proj", "mamba/conv",
        "mamba/x_proj", "mamba/dt", "mamba/scan", "mamba/gate", "mamba/out",
        "dattn/project", "dattn/attention", "dattn/combine", "dattn/out",
        "cross/project", "cross/attention", "cross/combine", "cross/out",
        "gmu/gate", "gmu/out", "dense_mlp", "lm_head"])


CASES = {
    "gather_embed_128": case_kernel(lambda one, w: _gather(one, w, False)),
    "gather_fm_128": case_kernel(lambda one, w: _gather(one, w, True)),
    "refused_width_16": case_refused_width(16),
    "refused_width_32": case_refused_width(32),
    "refused_width_64": case_refused_width(64),
    "decoder_v2_donated": case_decoder(V2_META, gathers=0),
    "decoder_compact_donated": case_decoder(V3_META, gathers=0),
    "decoder_compact_dict_donated": case_decoder(CTR_META, gathers=1),
    "fm_train_step_kstep1": case_train_step(1),
    "fm_train_step_kstep8": case_train_step(8),
    "fm_train_step_cell_2e24": case_train_step_cell,
    "serving_bucket_padded": case_serving_bucket(False),
    "serving_bucket_ragged": case_serving_bucket(True),
    "fm_mesh_dp2_mp2_step": case_mesh_step,
    "document_scorer_forward": case_document_scorer,
    "document_scorer_dsv3_forward": case_document_scorer_dsv3,
    "document_scorer_afmoe_forward": case_document_scorer_afmoe,
    "document_scorer_sambay_forward": case_document_scorer_sambay,
    "document_scorer_mellum_forward": case_document_scorer_mellum,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, topo):
    CASES[case](topo)


@pytest.mark.parametrize("width", [16, 32, 64, 128, 256])
def test_engine_rule_is_a_function_of_backend_and_width(width, monkeypatch):
    """``engine="auto"`` never probes: on a TPU backend it is Pallas
    exactly at the widths Mosaic lowers the row DMA for, XLA on every
    other backend, and a pin is passed through untouched.  Needs no TPU
    compiler — the compiles above hold the rule to the compiler."""
    monkeypatch.delenv("DMLC_RAGGED_ENGINE", raising=False)
    assert ragged_csr._resolve_engine("auto", width) == "xla"   # cpu here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    want = "pallas" if width % 128 == 0 else "xla"
    assert ragged_csr.mosaic_row_dma_ok(width) == (want == "pallas")
    assert ragged_csr._resolve_engine("auto", width) == want
    assert ragged_csr._resolve_engine("pallas", width) == "pallas"
