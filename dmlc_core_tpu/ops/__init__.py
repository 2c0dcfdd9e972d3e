"""TPU compute ops: XLA sparse CSR primitives + Pallas kernels."""

from .csr import csr_dense_matvec, csr_embed_sum, fm_pairwise  # noqa: F401

# NOTE: the bare `ring_attention`/`ulysses_attention` building-block fns
# are NOT re-exported here — their names collide with their submodules
# (Python binds a submodule as a package attribute on first import, which
# would shadow the function). Import them from the submodule:
#   from dmlc_core_tpu.ops.ring_attention import ring_attention
__all__ = ["csr_dense_matvec", "csr_embed_sum", "fm_pairwise",
           "ragged_segment_sum", "ragged_dense_matvec",
           "ragged_embed_sum", "ragged_fm_pairwise",
           "mask_ragged", "mask_batch",
           "make_ring_attention", "reference_attention",
           "make_ulysses_attention"]


def __getattr__(name):
    # heavyweight imports are lazy: pallas / shard_map machinery is not
    # needed for the pure-XLA paths
    import importlib
    lazy = {
        "ragged_segment_sum": "ragged_csr",
        "ragged_dense_matvec": "ragged_csr",
        "ragged_embed_sum": "ragged_csr",
        "ragged_fm_pairwise": "ragged_csr",
        "mask_ragged": "ragged_csr",
        "mask_batch": "ragged_csr",
        "make_ring_attention": "ring_attention",
        "reference_attention": "ring_attention",
        "make_ulysses_attention": "ulysses",
    }
    if name in lazy:
        mod = importlib.import_module(f".{lazy[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(name)
