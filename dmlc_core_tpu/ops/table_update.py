"""The exact dense update of a parameter table that a loss reads only at its
batch's rows, as one streaming Pallas kernel.

A loss that reads ``table[ids]`` has a gradient that is zero on every row the
batch did not name, yet an elementwise optimizer still moves every row: Adam's
moments decay whether a row was touched or not.  The dense form builds a zero
gradient as large as the table, scatters the batch's rows into it and passes
over parameters and moments twice more.  Here one kernel reads each block of
the table and of its optimizer state once, builds the block's gradient in VMEM
from the batch's deduplicated row gradients, applies the optimizer's own
elementwise update and writes the block back in place.  No dense gradient
exists, and the result is the dense update's.

* :func:`batch_rows` — the batch's ids deduplicated: the sorted unique rows
  ``uid`` and each entry's index ``inv`` into them; :func:`gather_rows` reads
  those rows of a table, as many as there are.
* :func:`plan` — the configured optimizer's update traced once and split at
  each table: what the step computes once from the optimizer's count (scalars,
  handed to the kernel) and the elementwise rest, which the kernel evaluates
  on each block.  There is no second copy of the optimizer's formula.
* the ``table_update`` kernel.  A table ``[N, D]`` is held by XLA with its
  rows as the minor dimension (``{0,1}``), so the kernel takes it as its free
  view ``[D, N]``, table rows on lanes; a vector ``[N]`` as ``[N / 128, 128]``.
  A block's gradient is placed by a one-hot product on the matrix unit: the
  rows are unique, so each output takes exactly one product, and the row
  gradients go in as an exact split into three bfloat16 parts, summed in the
  order that keeps every partial sum exact.  The block's gradient equals the
  scatter's bit for bit.  (A row gradient that is not finite reaches every
  row of its block, ``0 * inf``, where the scatter keeps it to its row; the
  loss is then not finite either.)
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import ClosedJaxpr, Literal, Var, jaxpr_as_fun

__all__ = ["batch_rows", "gather_rows", "plan", "TablePlan"]

LANES = 128
BLOCK = 8192                 # table rows a kernel step
_PIECE = 1024                # table rows the elementwise update takes at once
_GATHER = 1024               # unique rows gathered at a time
_SENTINEL = 2 ** 30          # pads ``uid``: no block's row
_ROWS_BUDGET = 48 * 2 ** 20  # VMEM the batch's row gradients may take
_VMEM_LIMIT = 100 * 2 ** 20

# primitives that read and write values of one shape element by element
_ELEMENTWISE = frozenset({
    "abs", "add", "and", "clamp", "convert_element_type", "copy", "div",
    "eq", "exp", "expm1", "ge", "gt", "integer_pow", "le", "log", "log1p",
    "logistic", "lt", "max", "min", "mul", "ne", "neg", "not", "or", "pow",
    "rsqrt", "select_n", "sign", "sqrt", "square", "sub", "tanh"})


def batch_rows(ids: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(uid, inv, count)`` of a batch's ``ids [nnz]``: ``uid`` the
    ``count`` sorted unique rows, padded to a multiple of ``_GATHER``
    entries with a row no table holds; ``inv [nnz]`` each entry's index into
    ``uid`` (``uid[inv] == ids``)."""
    nnz = ids.shape[0]
    cap = -(-nnz // _GATHER) * _GATHER
    sid, perm = lax.sort_key_val(ids.astype(jnp.int32),
                                 lax.iota(jnp.int32, nnz))
    new = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    pos = jnp.cumsum(new.astype(jnp.int32)) - 1
    # two more sorts, not scatters: 0.15 against 0.76 ms each on a v5e at
    # 163 840 ids
    _, inv = lax.sort_key_val(perm, pos)
    uid = jnp.sort(jnp.concatenate([
        jnp.where(new, sid, _SENTINEL),
        jnp.full((cap - nnz,), _SENTINEL, jnp.int32)]))
    return uid, inv, pos[-1] + 1


def gather_rows(table: jax.Array, uid: jax.Array, count: jax.Array
                ) -> jax.Array:
    """``table[uid]`` for the first ``count`` entries of ``uid``, zeros
    after: ``_GATHER`` rows at a time, for as many as ``count`` fills (a
    TPU gathers a row of a large table at a price; the batch's padding
    would pay it too)."""
    cap = uid.shape[0]
    last = table.shape[0] - 1

    def one(i, out):
        at = lax.dynamic_slice(uid, (i * _GATHER,), (_GATHER,))
        held = (at <= last).reshape((-1,) + (1,) * (table.ndim - 1))
        rows = jnp.where(held, table[jnp.minimum(at, last)], 0)
        return lax.dynamic_update_slice(out, rows,
                                        (i * _GATHER,) + (0,) * (rows.ndim - 1))
    return lax.fori_loop(0, (count + _GATHER - 1) // _GATHER, one,
                         jnp.zeros((cap,) + table.shape[1:], table.dtype))


# -- the optimizer's update, split at a table --------------------------------

def _live(eqns, outvars):
    """The equations ``outvars`` need, in order, and every variable they
    read."""
    need = {v for v in outvars if isinstance(v, Var)}
    kept = []
    for eqn in reversed(eqns):
        if any(o in need for o in eqn.outvars):
            kept.append(eqn)
            need.update(v for v in eqn.invars if isinstance(v, Var))
    return kept[::-1], need


def _read(env, v):
    return v.val if isinstance(v, Literal) else env[v]


class _LeafProgram:
    """One table's share of the update: ``known`` computes the scalars its
    elementwise rest reads (from the optimizer's count), ``body`` is that
    rest, evaluated on blocks of the leaf inside the kernel."""

    def __init__(self, known, body, ins, outs, resid):
        self.known, self.body = known, body
        self.ins, self.outs, self.resid = ins, outs, resid

    def run(self, values, resid):
        """``values`` in the order of ``ins`` (gradient, then the table's
        operands), any shape the block has; ``resid`` scalars."""
        env = dict(zip(self.ins, values))
        env.update(zip(self.resid, resid))
        shape = values[0].shape
        for eqn in self.body:
            args = [_read(env, v) for v in eqn.invars]
            if eqn.primitive.name == "broadcast_in_dim":
                out = [jnp.broadcast_to(args[0], shape)]
            else:
                out = eqn.primitive.bind(*args, **eqn.params)
                out = out if eqn.primitive.multiple_results else [out]
            env.update(zip(eqn.outvars, out))
        return [_read(env, v) for v in self.outs]


def _split_leaf(eqns, ins, outs, shape) -> Optional[_LeafProgram]:
    """Split the equations ``outs`` need into the part that reads no value of
    the leaf (scalars, computed once) and the elementwise part, or ``None``
    when a primitive that touches a value of the leaf's ``shape`` is not
    elementwise over it or the two parts meet in more than scalars."""
    eqns, _ = _live(eqns, outs)
    unknown = set(ins)
    known, body = [], []
    for eqn in eqns:
        reads = [v for v in eqn.invars if isinstance(v, Var)]
        if not (any(v in unknown for v in reads)
                or any(o.aval.shape == shape for o in eqn.outvars)):
            known.append(eqn)
            continue
        in_shapes = {v.aval.shape for v in eqn.invars}
        out_shapes = {o.aval.shape for o in eqn.outvars}
        if out_shapes != {shape}:
            return None
        if eqn.primitive.name == "broadcast_in_dim":
            if in_shapes != {()}:
                return None
        elif (eqn.primitive.name not in _ELEMENTWISE
              or not in_shapes <= {shape, ()}):
            return None
        body.append(eqn)
        unknown.update(eqn.outvars)
    resid = []
    for eqn in body:
        for v in eqn.invars:
            if isinstance(v, Var) and v not in unknown and v not in resid:
                if v.aval.shape != () or v.aval.dtype != jnp.float32:
                    return None
                resid.append(v)
    if any(isinstance(v, Var) and v not in unknown for v in outs):
        return None
    return _LeafProgram(_live(known, resid)[0], body, list(ins), list(outs),
                        resid)


class TablePlan:
    """The step's update of ``params`` and ``opt_state`` with ``tables``
    through the kernel and every other leaf through the traced update."""

    def __init__(self, closed, out_tree, tables, table_in, table_out, leaves,
                 block):
        self.closed, self.out_tree = closed, out_tree
        self.tables, self.block = tables, block
        self.table_in, self.table_out = table_in, table_out
        self.leaves = leaves
        jaxpr = closed.jaxpr
        fused = {i for k in tables for i in table_out[k]}
        self._rest_out = [i for i in range(len(jaxpr.outvars))
                          if i not in fused]
        eqns, self._rest_reads = _live(
            jaxpr.eqns, [jaxpr.outvars[i] for i in self._rest_out])
        self._rest = jaxpr_as_fun(ClosedJaxpr(jaxpr.replace(
            eqns=eqns, outvars=[jaxpr.outvars[i] for i in self._rest_out]),
            closed.consts))

    def _run_known(self, prog, flat):
        jaxpr = self.closed.jaxpr
        fn = jaxpr_as_fun(ClosedJaxpr(
            jaxpr.replace(eqns=prog.known, outvars=prog.resid),
            self.closed.consts))
        return fn(*self._inputs(flat, _live(prog.known, prog.resid)[1]))

    def _inputs(self, flat, reads):
        return [x if v in reads else None
                for v, x in zip(self.closed.jaxpr.invars, flat)]

    def apply(self, flat_inputs, rows_grads, uid):
        """``flat_inputs``: the traced update's inputs, flattened (gradients,
        state, parameters; the tables' dense gradients are never read);
        ``rows_grads[k]``: table ``k``'s gradient at ``uid`` rows.  Returns
        the update's outputs, unflattened (new parameters, new state)."""
        out = [None] * len(self.closed.jaxpr.outvars)
        rest = self._rest(*self._inputs(flat_inputs, self._rest_reads))
        for i, x in zip(self._rest_out, rest):
            out[i] = x
        resid = []
        for k in self.tables:
            resid += list(self._run_known(self.leaves[k], flat_inputs))
        resid = (jnp.stack([jnp.asarray(r, jnp.float32) for r in resid])
                 if resid else jnp.zeros((1,), jnp.float32))
        ops = [[flat_inputs[j] for j in self.table_in[k][1:]]
               for k in self.tables]
        with jax.named_scope("table_update"):
            new = _table_update(
                [self.leaves[k] for k in self.tables], resid, uid,
                [rows_grads[k] for k in self.tables], ops, self.block)
        for k, vals in zip(self.tables, new):
            for i, x in zip(self.table_out[k], vals):
                out[i] = x
        return jax.tree.unflatten(self.out_tree, out)


def _block_rows(num_rows: int) -> int:
    """The largest block of at most ``BLOCK`` rows that divides the table
    into whole pieces, or 0."""
    block = BLOCK
    while block >= _PIECE and num_rows % block:
        block //= 2
    return block if block >= _PIECE else 0


def plan(update: Callable, params: Dict, opt_state, tables: Sequence[str],
         nnz: int) -> Tuple[Optional[TablePlan], str]:
    """``(plan, why)``: how ``update(grads, opt_state, params) -> (params,
    opt_state)`` runs with ``tables`` (leaves of the dict ``params`` that
    the loss reads only at the batch's ``nnz`` ids) through the kernel, or
    ``None`` and the reason it cannot."""
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    params = jax.tree.map(sds, params)
    opt_state = jax.tree.map(sds, opt_state)
    if not tables:
        return None, "the model declares no table"
    shapes = [params[k].shape for k in tables]
    if any(params[k].dtype != jnp.float32 or len(s) not in (1, 2)
           for k, s in zip(tables, shapes)):
        return None, "a table is not a float32 vector or matrix"
    num_rows = shapes[0][0]
    block = _block_rows(num_rows)
    if any(s[0] != num_rows for s in shapes) or not block:
        return None, f"tables of {num_rows} rows do not divide into blocks"
    width = sum(s[1] if len(s) == 2 else 1 for s in shapes)
    if width * -(-nnz // _GATHER) * _GATHER * 4 > _ROWS_BUDGET:
        return None, f"{nnz} row gradients do not fit the kernel's VMEM"
    with jax.disable_jit():
        closed, out_shape = jax.make_jaxpr(update, return_shape=True)(
            params, opt_state, params)
    jaxpr = closed.jaxpr
    p_paths = [jax.tree_util.keystr(p) for p, _ in
               jax.tree_util.tree_flatten_with_path(params)[0]]
    s_paths = [jax.tree_util.keystr(p) for p, _ in
               jax.tree_util.tree_flatten_with_path(opt_state)[0]]
    n_p, n_s = len(p_paths), len(s_paths)
    s_leaves = jax.tree.leaves(opt_state)
    table_in, table_out, leaves = {}, {}, {}
    for k, shape in zip(tables, shapes):
        key = f"[{k!r}]"
        ip = p_paths.index(key)
        own = [j for j, (path, leaf) in enumerate(zip(s_paths, s_leaves))
               if path.endswith(key) and leaf.shape == shape]
        # inputs: gradient, state leaves, parameter; outputs: state leaves,
        # parameter, each in its input's place
        table_in[k] = [ip] + [n_p + j for j in own] + [n_p + n_s + ip]
        table_out[k] = [n_p + j for j in own] + [ip]
        prog = _split_leaf(jaxpr.eqns,
                           [jaxpr.invars[i] for i in table_in[k]],
                           [jaxpr.outvars[i] for i in table_out[k]], shape)
        if prog is None:
            return None, f"the optimizer's update of {k!r} is not elementwise"
        leaves[k] = prog
    fused_in = {jaxpr.invars[i] for k in tables for i in table_in[k]}
    p = TablePlan(closed, jax.tree.structure(out_shape), tuple(tables),
                  table_in, table_out, leaves, block)
    if fused_in & p._rest_reads:
        return None, "the optimizer's other leaves read a table"
    return p, ""


# -- the kernel ----------------------------------------------------------------

def _one_hot(mask):
    return mask.astype(jnp.float32).astype(jnp.bfloat16)


def _place(g, hot):
    """``g [M, 128] @ hot [X, 128]^T`` in float32, exactly where every column
    of ``hot`` holds at most one 1: ``g`` as three bfloat16 parts, each
    product exact, the parts summed high to low (every partial sum is then a
    prefix of ``g``'s 24 bits)."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    hi = g.astype(bf16)
    r = g - hi.astype(f32)
    mid = r.astype(bf16)
    lo = (r - mid.astype(f32)).astype(bf16)
    m = g.shape[0]
    out = lax.dot_general(jnp.concatenate([hi, mid, lo]), hot,
                          (((1,), (1,)), ((), ())), preferred_element_type=f32)
    return (out[:m] + out[m:2 * m]) + out[2 * m:]


def _kernel(firsts_ref, resid_ref, uid_ref, *refs, leaves, wide, counts,
            offsets, block):
    n = len(leaves)
    rows_refs, refs = refs[:n], refs[n:]
    n_in = sum(counts)
    in_refs, out_refs = refs[:n_in], refs[n_in:2 * n_in]
    acc_refs, at_ref = refs[2 * n_in:-1], refs[-1]
    i = pl.program_id(0)
    base = i * block
    for acc in acc_refs:
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    # the chunks of 128 unique rows that reach into this block: blocks go
    # in order, so the first is found from the last block's
    @pl.when(i == 0)
    def _():
        at_ref[0] = 0

    lo = lax.while_loop(lambda c: firsts_ref[c + 1] <= base,
                        lambda c: c + 1, at_ref[0])
    hi = lax.while_loop(lambda c: firsts_ref[c] < base + block,
                        lambda c: c + 1, lo)
    at_ref[0] = lo

    def chunk(c, _):
        u = uid_ref[pl.ds(c, 1), :] - base                      # [1, 128]
        for is_wide, rows, acc in zip(wide, rows_refs, acc_refs):
            if is_wide:
                hot = _one_hot(lax.broadcasted_iota(
                    jnp.int32, (block, LANES), 0) == u)
                g = rows[:, pl.ds(pl.multiple_of(c * LANES, LANES), LANES)]
                acc[...] += _place(g, hot)
            else:
                at_row = lax.broadcasted_iota(
                    jnp.int32, (block // LANES, LANES), 0) == (u >> 7)
                g = jnp.where(at_row, rows[pl.ds(c, 1), :], 0.0)
                hot = _one_hot(lax.broadcasted_iota(
                    jnp.int32, (LANES, LANES), 0) == (u & (LANES - 1)))
                acc[...] += _place(g, hot)

    lax.fori_loop(lo, hi, chunk, None)

    def piece(q, _):
        at = 0
        for leaf, is_wide, count, off, acc in zip(leaves, wide, counts,
                                                 offsets, acc_refs):
            if is_wide:
                sl = (slice(None), pl.ds(pl.multiple_of(q * _PIECE, _PIECE),
                                         _PIECE))
            else:
                rows = _PIECE // LANES
                sl = (pl.ds(pl.multiple_of(q * rows, rows), rows),
                      slice(None))
            values = [acc[sl]] + [r[sl] for r in in_refs[at:at + count]]
            resid = [resid_ref[off + j] for j in range(len(leaf.resid))]
            for r, x in zip(out_refs[at:at + count], leaf.run(values, resid)):
                r[sl] = x.astype(r.dtype)
            at += count

    lax.fori_loop(0, block // _PIECE, piece, None)


def _table_update(leaves: List[_LeafProgram], resid, uid, rows_grads,
                  operands, block, interpret=False):
    """Every table's operands after one update.  ``operands[t]`` are table
    ``t``'s arrays in its program's order (state leaves, parameter), each
    ``[N]`` or ``[N, D]``; ``rows_grads[t]`` its gradient at ``uid``."""
    num_rows = operands[0][0].shape[0]
    wide = [ops[0].ndim == 2 for ops in operands]

    def view(x, is_wide):
        return x.T if is_wide else x.reshape(-1, LANES)

    def spec(x, is_wide):
        if is_wide:
            return pl.BlockSpec((x.shape[1], block), lambda i, *_: (0, i))
        return pl.BlockSpec((block // LANES, LANES), lambda i, *_: (i, 0))

    counts = [len(ops) for ops in operands]
    offsets = [0]
    for leaf in leaves:
        offsets.append(offsets[-1] + len(leaf.resid))
    flat = [x for ops in operands for x in ops]
    flat_wide = [w for w, ops in zip(wide, operands) for _ in ops]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    n_rows = len(rows_grads)
    ins = [resid, uid.reshape(-1, LANES)] + [
        view(g, w) for g, w in zip(rows_grads, wide)] + [
        view(x, w) for x, w in zip(flat, flat_wide)]
    first = 1 + 2 + n_rows           # the operands' index among the inputs
    # each chunk's first row, and past the last a row no block holds
    firsts = jnp.concatenate([uid[::LANES],
                              jnp.full((1,), _SENTINEL, jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_kernel, leaves=leaves, wide=wide, counts=counts,
                          offsets=offsets, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(num_rows // block,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), whole]
            + [whole] * n_rows
            + [spec(x, w) for x, w in zip(flat, flat_wide)],
            out_specs=[spec(x, w) for x, w in zip(flat, flat_wide)],
            scratch_shapes=[
                pltpu.VMEM((ops[0].shape[1], block) if w
                           else (block // LANES, LANES), jnp.float32)
                for w, ops in zip(wide, operands)]
            + [pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(view(x, w).shape, x.dtype)
                   for x, w in zip(flat, flat_wide)],
        input_output_aliases={first + j: j for j in range(len(flat))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="table_update",
    )(firsts, *ins)
    news, at = [], 0
    for ops in operands:
        news.append([o.T if o.ndim == 2 and x.ndim == 2 else o.reshape(x.shape)
                     for o, x in zip(out[at:at + len(ops)], ops)])
        at += len(ops)
    return news
