"""Ahead-of-time compiles of the document scorers for a *described* TPU v5e,
one case a benchmark configuration (see ``test_tpu_compile.py`` for what a
described-chip compile shows and what it cannot).

Each scorer is compiled at the widths its configuration file publishes and
the sizes its cell times, with bfloat16 parameters on one chip.  Every case
holds the program to the same skeleton: the parameter count, a bound on the
bytes it holds in HBM beside them, each Pallas kernel selected at lowering
as many times as the model has layers of its kind and under the scope a
trace reads, neither the ``[T, T]`` scores nor the ``[T, V]`` logits held
whole, and every scope a trace reads present.  A row of ``SCORERS`` says
what that means for one configuration; its ``own`` function checks what
only that family has.
"""

import os
import re
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, NamedTuple, Optional

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import BENCH, HBM_BYTES, batch_shapes, hbm, names_scopes, on
from dmlc_core_tpu.models import hybrid_lm, sambay_lm
from dmlc_core_tpu.models.hybrid_lm import HybridMoELM
from dmlc_core_tpu.models.sambay_lm import SambaYLM


def _program_digest(compiled) -> str:
    """The compiled program's text without what says where it came from:
    the tables of source files and stack frames, each instruction's
    ``metadata`` and the custom calls' ``backend_config`` (a Pallas
    kernel's serialised body carries its source lines); what is left is
    every instruction, shape, layout and schedule."""
    import hashlib
    head, _, body = compiled.as_text().partition("\n")
    text = head + "\n" + body[re.search(r"^(%|ENTRY)", body, re.M).start():]
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r', backend_config=(\{.*?\}|"(?:[^"\\]|\\.)*")(?=[,\n])',
                  "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _dims(text):
    """The dimensions of every instruction's result in ``text``."""
    return [[int(x) for x in dims.split(",")] for dims in re.findall(
        r"^\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]", text, re.M)]


def _kernels(text, name):
    return [line for line in text.splitlines()
            if "custom-call(" in line and f" %{name}" in line]


def _attention_is_the_kernel(text, model, mixer):
    """Every ``mixer`` layer's attention is one ``doc_attention`` Pallas
    kernel, selected at lowering, under the scope a trace reads, and no
    loop of the ``jnp`` walk is left under that scope."""
    kernels = _kernels(text, "doc_attention")
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
    kernels = _kernels(text, "moe_combine")
    assert len(kernels) == model.layers - model.dense_layers
    for line in kernels:
        assert 'custom_call_target="tpu_custom_call"' in line
        assert re.search(r'op_name="[^"]*moe/combine/', line), line[:300]
    sums = rf"f32\[{t},(?:{model.hidden}|{model.hidden // 128},128)\]"
    assert not re.search(rf"= {sums}\S* scatter\(", text)


def _no_routed_buffer(text, model, t):
    """The experts' rows are carried in blocks: no ``[T * k, H]`` buffer."""
    assignments = t * model.top_k
    for d in _dims(text):
        assert not (assignments in d and model.hidden in d), d


def _kimi(compiled, text, model, t, args):
    _attention_is_the_kernel(text, model, "mla")
    # half the experts held, and no ``[T * k, H]`` buffer: the temporaries
    # are under the whole buffer's 3.61 GB
    _combine_is_the_kernel(text, model, t)
    assert compiled.memory_analysis().temp_size_in_bytes < 3_610_738_176
    # the chunk's [N, C, H, d] <-> [N, H, C, d] copies went with the jnp
    # formulation: the kernel cuts its tiles from [T, H * d]
    chunks = t // 64
    for d in _dims(text):
        assert not (len(d) == 4 and d[0] == chunks), d


def _dsv3(compiled, text, model, t, args):
    _attention_is_the_kernel(text, model, "mla")
    _combine_is_the_kernel(text, model, t)
    _no_routed_buffer(text, model, t)


def _afmoe(compiled, text, model, t, args):
    _attention_is_the_kernel(text, model, "gqa")
    _combine_is_the_kernel(text, model, t)
    _no_routed_buffer(text, model, t)
    # keys and values leave the projections 8 heads wide and reach the
    # kernel so, [T, 8 * 128] and [8, 128, T] beside q's [48, 128, T]
    for line in _kernels(text, "doc_attention"):
        assert f"bf16[{t},{model.kv_heads * model.head_dim}]" in line
        assert f"bf16[{model.kv_heads},{model.head_dim},{t}]" in line
        assert f"bf16[{model.heads},{model.head_dim},{t}]" in line


def _mellum(compiled, text, model, t, args):
    # experts: the grouped-matmul kernel (in ``kernels``) over blocks of
    # one mean run of rows (T k / E)
    block = t * model.top_k // model.experts
    assert f"bf16[{block},{model.hidden}]" in text      # a block's rows
    _attention_is_the_kernel(text, model, "gqa")
    _combine_is_the_kernel(text, model, t)
    # by token: the loop carries the rows in sorted order as words (nine
    # word rows of bfloat16 pairs a row), not the [T, H / 128, 128] sums
    words = model.hidden // 256
    assert f"u32[{t * model.top_k * words},128]" in text
    assert f"f32[{t},{model.hidden // 128},128]" not in text


def _sambay(compiled, text, model, t, args):
    # the kernels' flags fold to the counters' constants in the chip's program
    entry = text[text.index("\nENTRY"):]
    root = re.sub(r"/\*.*?\*/", "", re.search(
        r"ROOT %[\w.\-]+ = .*? tuple\(([^)]*)\)", entry).group(1)).split(",")

    def constant(name):
        line = re.search(rf"^\s+{re.escape(name)} = (.*)$", entry, re.M)[1]
        m = re.search(r" (?:constant\((\d+)\)|copy\((%[\w.\-]+)\))", line)
        return int(m[1]) if m and m[1] else constant(m[2]) if m else None

    paths, _ = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(model.forward_counted, *args))
    names = [jax.tree_util.keystr(p) for p, _ in paths]
    for counter, want in (("ssm.fused_layers", 9), ("dattn.fused_layers", 16)):
        assert constant(root[names.index(f"[1]['{counter}']")].strip()) \
            == want, counter
    for d in _dims(text):
        assert not (t in d and model.d_state in d
                    and model.d_inner in d), d             # [T, C, N]


def _brumby(compiled, text, model, t, args):
    # retention never holds the symmetric square of a token's key or query
    # in HBM: no buffer of its 8 256 features (8 320 as the kernel lays
    # them out), and the kernel writes bfloat16 [T, H d] alone
    features = model.head_dim * (model.head_dim + 1) // 2
    for d in _dims(text):
        assert features not in d and features + 64 not in d, d
    for line in _kernels(text, "power_retention"):
        assert re.search(rf"= bf16\[{t},{model.heads * model.head_dim}\]",
                         line), line[:200]
    # the dense layer's float32 gate and up in two halves of the tokens
    width = 2 * model.dense_width
    assert f"f32[{t // 2},{width}]" in text
    assert f"f32[{t},{width}]" not in text


class Scorer(NamedTuple):
    """One configuration's compile: ``config`` under
    ``benchmarks/chip/configs`` read by ``load_arch`` into a ``model``;
    ``t`` tokens in ``rows`` documents, the cell's timed batch."""
    load_arch: Callable
    model: type
    config: str
    t: int
    rows: int
    params: int                     # parameters, every one held on the chip
    hbm: int                        # bytes the program holds, at most
    kernels: dict                   # Pallas kernel -> (its scope, how many)
    scopes: tuple                   # what ``names_scopes`` has to find
    present: tuple = ()             # text the program holds
    absent: tuple = ()              # text it does not
    absent_ops: tuple = ()          # scopes no instruction carries
    # the program before the class learnt the ``mellum`` type (per-kind
    # rotation, softmax routing, optional block parts), by
    # ``_program_digest``: the files before it have to compile to the same
    # programs, instruction for instruction
    digest: Optional[str] = None
    own: Optional[Callable] = None  # (compiled, text, model, t, args)


SCORERS = {
    # layers 1-5, 128 of 256 experts, half the vocabulary, 16 documents in
    # 32 768 tokens: 8.57 GB of parameters.  15.41 GB with the temporaries
    # of the jnp chunk; the kernel keeps them in VMEM: 12.18 GB, with
    # attention's kernel too
    "document_scorer_forward": Scorer(
        hybrid_lm.load_arch, HybridMoELM, "kimi_linear_48b_ep2_l5", 32768, 16,
        params=4_282_936_192, hbm=12_200_000_000,
        kernels={"kda_chunk": ("kda/scan", 4)},
        scopes=("lm_embed", "kda/conv", "kda/gates", "kda/scan",
                "mla/project", "mla/attention", "moe/router", "moe/dispatch",
                "moe/experts", "moe/shared", "moe/combine", "dense_mlp",
                "lm_head"),
        present=("ragged-dot",),            # experts: grouped products
        digest="a589a989ec647723", own=_kimi),
    # one dense and four mixture layers, 16 of 256 experts, an eighth of
    # the vocabulary, 8 documents in 16 384 tokens: 8.583 GB of arguments
    # + 3.177 GB of temporaries, 3.008 with attention's kernel
    "document_scorer_dsv3_forward": Scorer(
        hybrid_lm.load_arch, HybridMoELM, "gigachat31_702b_ep16_l5", 16384, 8,
        params=4_291_256_320, hbm=11_760_000_000, kernels={},
        scopes=("lm_embed", "mla/q_lora", "mla/project", "mla/rope",
                "mla/attention", "mla/out", "moe/router", "moe/dispatch",
                "moe/experts", "moe/shared", "moe/combine", "dense_mlp",
                "lm_head"),
        present=("ragged-dot",), absent=(" %kda_chunk",),
        digest="e963a16bcd630264", own=_dsv3),
    # one dense and four mixture layers, 32 of 256 experts, an eighth of
    # the vocabulary, 8 documents in 32 768 tokens: 8.644 GB of arguments
    # + the temporaries (reckoned at 12-13.5 GB in all before the first
    # compile); no key or value array repeated to the 48 query heads
    "document_scorer_afmoe_forward": Scorer(
        hybrid_lm.load_arch, HybridMoELM, "trinity_large_400b_ep8_l5",
        32768, 8,
        params=4_321_903_872, hbm=13_500_000_000, kernels={},
        scopes=("lm_embed", "gqa/project", "gqa/qk_norm", "gqa/rope",
                "gqa/attention", "gqa/gate", "gqa/out", "post_norm",
                "moe/router", "moe/dispatch", "moe/experts", "moe/shared",
                "moe/combine", "dense_mlp", "lm_head"),
        present=("ragged-dot",), absent=(" %kda_chunk",),
        digest="e5ec9287337ee361", own=_afmoe),
    # eight layers, all 64 experts of each, the whole vocabulary, 8
    # documents in 32 768 tokens: 7.59 GB of arguments + the temporaries
    # (reckoned at 10.5-11.5 GB in all before the first compile).  Every
    # mixture layer's products are the grouped-matmul kernel and it sums
    # its rows by token through the combine kernel, once after its loop;
    # the full layers turn under ``gqa/rope_yarn``, the sliding ones under
    # ``gqa/rope``; no gate, QK-norm, post-norm or shared expert exists
    "document_scorer_mellum_forward": Scorer(
        hybrid_lm.load_arch, HybridMoELM, "mellum2_12b_a2.5b_l8", 32768, 8,
        params=3_794_966_784, hbm=12_000_000_000,
        kernels={"gmm": ("moe/experts", 16)},
        scopes=("lm_embed", "gqa/project", "gqa/rope", "gqa/rope_yarn",
                "gqa/attention", "gqa/out", "moe/router", "moe/dispatch",
                "moe/experts", "moe/combine", "lm_head"),
        absent=("ragged-dot",),
        absent_ops=("gqa/gate", "gqa/qk_norm/", "post_norm", "moe/shared"),
        own=_mellum),
    # eight layers, the whole vocabulary, 4 documents in 32 768 tokens:
    # 8.40 GB of arguments + 3.67 GB of temporaries (12.07 GB; 14.34 GB
    # before the dense layer's products were halved).  Every layer's
    # retention is one ``power_retention`` kernel under the scope a trace
    # reads; no attention, no experts
    "document_scorer_brumby_forward": Scorer(
        hybrid_lm.load_arch, HybridMoELM, "brumby_14b_base_l8", 32768, 4,
        params=4_198_652_992, hbm=12_500_000_000,
        kernels={"power_retention": ("retention/kernel", 8)},
        scopes=("lm_embed", "retention/project", "retention/qk_norm_rope",
                "retention/gate", "retention/kernel", "retention/out",
                "dense_mlp", "lm_head"),
        absent=("ragged-dot", " %doc_attention", " %kda_chunk"),
        absent_ops=("gqa/", "mla/", "moe/"),
        own=_brumby),
    # the SambaY scorer whole: 32 layers, the whole tied vocabulary, 8
    # documents in 32 768 tokens: 7.706 GB of arguments + 3.545 GB of
    # temporaries (reckoned at 4-5 GB of temporaries before the first
    # compile); no ``[T, C, N]`` state held whole
    "document_scorer_sambay_forward": Scorer(
        sambay_lm.load_arch, SambaYLM, "phi4_mini_flash_3.8b", 32768, 8,
        params=3_852_562_944, hbm=11_300_000_000,
        kernels={"selective_scan": ("mamba/scan", 9),
                 "diff_attention": ("(dattn|cross)/attention", 16)},
        scopes=("lm_embed", "layer_norm", "mamba/in_proj", "mamba/conv",
                "mamba/x_proj", "mamba/dt", "mamba/scan", "mamba/gate",
                "mamba/out", "dattn/project", "dattn/attention",
                "dattn/combine", "dattn/out", "cross/project",
                "cross/attention", "cross/combine", "cross/out", "gmu/gate",
                "gmu/out", "dense_mlp", "lm_head"),
        absent=(" %doc_attention",),        # 64-wide heads: the pairs'
        own=_sambay),
}


def _compile(c, topo):
    model = c.model(c.load_arch(
        os.path.join(BENCH, "configs", f"{c.config}.json")))
    one = SingleDeviceSharding(topo.devices[0])
    S = jax.ShapeDtypeStruct
    params = jax.tree.map(lambda shape: S(shape, model.dtype, sharding=one),
                          model.shapes(),
                          is_leaf=lambda x: isinstance(x, tuple))
    batch = on(one, dict(batch_shapes(c.rows, c.t),
                         row_ptr=S((c.rows + 1,), jnp.int32)))
    compiled = jax.jit(model.forward_counted).lower(params, batch).compile()
    return model, params, batch, compiled


@pytest.fixture(scope="module")
def programs(topo, request):
    """The selected cases' programs, compiled side by side: these compiles
    are the suite's longest tests, and ``--dist loadfile`` starts a file of
    few tests late, so one after another they would end the whole run."""
    cases = sorted({item.callspec.params["case"]
                    for item in request.session.items
                    if item.module is request.module})
    with ThreadPoolExecutor(len(cases)) as pool:
        yield {case: pool.submit(_compile, SCORERS[case], topo)
               for case in cases}


@pytest.mark.parametrize("case", sorted(SCORERS))
def test_compiles_for_v5e(case, programs):
    c = SCORERS[case]
    model, params, batch, compiled = programs[case].result()
    assert sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(params)) \
        == c.params
    if c.digest is not None:
        assert _program_digest(compiled) == c.digest
    assert hbm(compiled) < c.hbm < HBM_BYTES
    text = compiled.as_text()
    # each kernel selected at lowering (this process's backend is the
    # CPU), once a layer of its kind, under the scope a trace reads
    for name, (scope, count) in c.kernels.items():
        kernels = _kernels(text, name)
        assert len(kernels) == count, name
        for line in kernels:
            assert 'custom_call_target="tpu_custom_call"' in line
            assert re.search(rf'op_name="[^"]*{scope}/', line), line[:300]
    for fragment in c.present:
        assert fragment in text, fragment
    for fragment in c.absent:
        assert fragment not in text, fragment
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    for absent in c.absent_ops:
        assert absent not in op_names, absent
    for d in _dims(text):
        assert not (c.t in d and model.vocab in d), d       # [T, V]
        assert d.count(c.t) < 2, d                          # [T, T]
    if c.own is not None:
        c.own(compiled, text, model, c.t, (params, batch))
    names_scopes(compiled, c.scopes)
