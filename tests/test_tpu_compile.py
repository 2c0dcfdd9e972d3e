"""Ahead-of-time compiles of the main path for a *described* TPU v5e.

The TPU's compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached (on-chip-measurement guide §2.3).  That
shows what ``interpret=True`` cannot: a Pallas kernel whose row DMA is not
aligned to the 128-lane tiling is refused here exactly as on the chip, and
a program that does not fit 16 GB of HBM is refused too.  Nothing runs, so
these cases say nothing about results or times — ``chip_smoke.py`` on the
chip does that.  Code that asks ``jax.default_backend()`` still sees the
CPU, so each case compiles the kernel or the jitted step itself.  The
document scorers' compiles are in ``test_tpu_compile_lm.py``.
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

from conftest import HBM_BYTES, batch_shapes, hbm, names_scopes, on
from dmlc_core_tpu.models import (FactorizationMachine, make_train_step,
                                  make_train_step_fused, param_shardings)
from dmlc_core_tpu.ops import ragged_csr
from dmlc_core_tpu.pipeline.device_loader import (_fused_words_meta,
                                                  make_decoder)

ROWS, NNZ, F, D = 4096, 131072, 1 << 20, 32      # the smoke's shapes
V2_META = 98304                    # v2 wire: a mid nnz bucket, raw ids
V3_META = 98304 | (20 << 32)       # compact wire: 20-bit ids, raw f32 vals
# the benchmark's CTR cells: 24-bit ids, 10-bit dictionary codes
CTR_META = 163840 | (24 << 32) | (10 << 40)


def _fm_state(model, opt):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return params, jax.eval_shape(opt.init, params)


def _gather(one, width, fm):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    args = on(one, (S((NNZ,), i32), S((NNZ,), i32), S((NNZ,), f32),
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
        assert hbm(compiled) < HBM_BYTES
        text = compiled.as_text()
        assert "tpu_custom_call" not in text
        assert "HloModule jit__unpack" in text
        names_scopes(compiled, ["wire_decode/ids", "wire_decode/vals",
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
        params, opt_state = on(one, _fm_state(model, opt))
        if kstep == 1:
            lowered = make_train_step(model, opt).lower(
                params, opt_state, on(one, batch_shapes(rows, nnz)))
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
        assert hbm(compiled) < HBM_BYTES
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
        names_scopes(compiled, [
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
            on(one, params), on(one, engine._batch_avals(bucket))).compile()
        assert hbm(compiled) < HBM_BYTES
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
    batch = on(NamedSharding(mesh, P("dp")), batch_shapes(ROWS, NNZ))
    compiled = make_train_step(model, opt, mesh).lower(
        params, opt_state, batch).compile()
    assert "all-reduce" in compiled.as_text()
    # a meshed step keeps the dense update: no kernel
    assert "tpu_custom_call" not in compiled.as_text()
    assert hbm(compiled) < HBM_BYTES      # bytes on each device


CASES = {
    "gather_embed_128": case_kernel(lambda one, w: _gather(one, w, False)),
    "gather_fm_128": case_kernel(lambda one, w: _gather(one, w, True)),
    "refused_width_16": case_refused_width(16),
    "refused_width_32": case_refused_width(32),
    "refused_width_64": case_refused_width(64),
    "decoder_v2_donated": case_decoder(V2_META, gathers=0),
    "decoder_compact_donated": case_decoder(V3_META, gathers=0),
    "decoder_compact_dict_donated": case_decoder(CTR_META, gathers=1),
    "fm_train_step_kstep8": case_train_step(8),
    "fm_train_step_cell_2e24": case_train_step_cell,
    "serving_bucket_padded": case_serving_bucket(False),
    "serving_bucket_ragged": case_serving_bucket(True),
    "fm_mesh_dp2_mp2_step": case_mesh_step,
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
