"""The jaxpr effect linter: trace the jitted entry points, walk every
(nested) jaxpr, and flag effect-level hazards the unit tests cannot see
from output values alone.

What is checked, per traced entry point:

* **launch contract** (:func:`check_launch_contract`): the forward wrapper
  lowers to exactly ONE ``pallas_call`` (the paper's fused single-launch
  claim) and its gradient to exactly three (forward replay for residual
  recompute is forbidden — dQ and dK/dV walk the saved stats).
* **scatter modes** (:func:`check_scatter_modes`): a ``scatter-add`` with
  ``unique_indices=True`` is a write-write race — the dK/dV scatter twin
  and the packed transposed walk *rely* on duplicate owner tiles
  accumulating; an overwrite ``scatter`` with ``unique_indices=True``
  breaks the paged-slab null-page contract, where every inactive row's
  write deliberately collides on page 0.
* **psum dtype** (:func:`check_psum_dtype`): any floating ``psum`` operand
  narrower than f32 means partial ``(out, m, l)`` triples were downcast
  before the cross-shard merge — the masked psum must combine f32.
* **double dequant** (:func:`check_double_dequant`): one int8 value
  widened by two separate ``convert_element_type`` equations in the same
  jaxpr is the int8-slab double-dequant bug shape (scale applied twice).
* **shard_map reductions** (:func:`check_shard_map_reduction`): a
  ``shard_map`` region with sharded inputs, replicated outputs, and NO
  collective anywhere inside is letting unreduced partials escape.
* **write ownership** (:func:`check_write_ownership`): a numeric probe of
  the decode write routing — for every shard index and every cache
  position, the physical write target must be the owner's page or the
  null page 0, never another shard's storage.
* **VMEM budget** (:func:`check_vmem`): per-``pallas_call`` resident-block
  estimates (the kernels' block shapes padded to the TPU (sublane, lane)
  tiling, pipelined blocks double-buffered, f32 scratch) against the
  16 MiB VMEM budget.

Pure stdlib + jax tracing: nothing here executes a kernel.
"""
from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from repro.analysis import Finding

VMEM_BUDGET = 16 * 2 ** 20       # bytes of VMEM one core can hold resident
LANES = 128                      # TPU lane width (decode stat blocks)

_COLLECTIVES = ("psum", "pmax", "pmin", "all_gather", "reduce_scatter",
                "ppermute", "all_to_all", "psum_scatter")


# ---------------------------------------------------------------------- #
# Generic jaxpr walking (duck-typed: survives jax API renames)
# ---------------------------------------------------------------------- #
def _as_jaxpr(obj) -> Optional[Any]:
    if hasattr(obj, "jaxpr") and hasattr(obj, "consts"):   # ClosedJaxpr
        return obj.jaxpr
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):    # Jaxpr
        return obj
    return None


def walk_jaxprs(obj) -> Iterator[Any]:
    """Yield ``obj``'s jaxpr and every jaxpr nested in equation params
    (scan/cond/while/pjit/shard_map/custom_vjp bodies), depth-first,
    each distinct jaxpr once."""
    seen: set = set()

    def rec(o):
        j = _as_jaxpr(o)
        if j is None:
            if isinstance(o, (tuple, list)):
                for x in o:
                    rec(x)
            return
        if id(j) in seen:
            return
        seen.add(id(j))
        yield_list.append(j)
        for eqn in j.eqns:
            for p in eqn.params.values():
                rec(p)

    yield_list: List[Any] = []
    rec(obj)
    return iter(yield_list)


def iter_eqns(obj) -> Iterator[Any]:
    for j in walk_jaxprs(obj):
        for eqn in j.eqns:
            yield eqn


def count_primitive(obj, name: str) -> int:
    return sum(1 for e in iter_eqns(obj) if e.primitive.name == name)


def _dtype_of(var) -> Optional[np.dtype]:
    aval = getattr(var, "aval", None)
    dt = getattr(aval, "dtype", None)
    return np.dtype(dt) if dt is not None else None


# ---------------------------------------------------------------------- #
# Launch contract
# ---------------------------------------------------------------------- #
def check_launch_contract(pattern, n: int, block_q: int, block_k: int,
                          target: str = "") -> List[Finding]:
    """Forward = 1 ``pallas_call``, grad = 3 (dQ + packed dK/dV + the
    forward's own launch replayed for residuals is NOT allowed — the
    third launch is the grad-time forward of ``custom_vjp`` residual
    plumbing, i.e. fwd(1) + dq(1) + dkv(1))."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import LAUNCH_CONTRACT, salo_attention

    findings: List[Finding] = []
    d = 16
    q = jnp.zeros((1, n, d), jnp.float32)

    fwd = jax.make_jaxpr(
        lambda a, b, c: salo_attention(a, b, c, pattern, block_q, block_k,
                                       None, True))(q, q, q)
    n_fwd = count_primitive(fwd, "pallas_call")
    if n_fwd != LAUNCH_CONTRACT["forward"]:
        findings.append(Finding(
            "launch-contract", target,
            f"forward lowers to {n_fwd} pallas_call launches, the fused "
            f"single-launch contract requires exactly "
            f"{LAUNCH_CONTRACT['forward']}"))

    grad = jax.make_jaxpr(jax.grad(
        lambda a, b, c: salo_attention(a, b, c, pattern, block_q, block_k,
                                       None, True).sum(),
        argnums=(0, 1, 2)))(q, q, q)
    n_grad = count_primitive(grad, "pallas_call")
    if n_grad != LAUNCH_CONTRACT["grad"]:
        findings.append(Finding(
            "launch-contract", target,
            f"gradient lowers to {n_grad} pallas_call launches, the "
            f"no-forward-recompute contract requires exactly "
            f"{LAUNCH_CONTRACT['grad']} (fwd + dQ + dK/dV)"))
    return findings


# ---------------------------------------------------------------------- #
# Effect checks over an arbitrary traced jaxpr
# ---------------------------------------------------------------------- #
def check_scatter_modes(traced, target: str = "") -> List[Finding]:
    findings: List[Finding] = []
    for eqn in iter_eqns(traced):
        name = eqn.primitive.name
        if name not in ("scatter-add", "scatter", "scatter-max",
                        "scatter-mul", "scatter-min"):
            continue
        if not eqn.params.get("unique_indices", False):
            continue
        if name == "scatter-add":
            findings.append(Finding(
                "scatter-race", target,
                "scatter-add with unique_indices=True: duplicate owner "
                "tiles across packed rows make this a write-write race"))
        else:
            findings.append(Finding(
                "scatter-race", target,
                f"{name} with unique_indices=True: the paged-slab write "
                f"path relies on harmless null-page-0 collisions"))
    return findings


def check_psum_dtype(traced, target: str = "") -> List[Finding]:
    findings: List[Finding] = []
    for eqn in iter_eqns(traced):
        if eqn.primitive.name != "psum":
            continue
        import jax.numpy as jnp
        for var in eqn.invars:
            dt = _dtype_of(var)
            if dt is not None and jnp.issubdtype(dt, jnp.floating) \
                    and dt != np.float32 and dt != np.float64:
                findings.append(Finding(
                    "psum-dtype", target,
                    f"psum over {dt} operand: partial (out, m, l) stats "
                    f"must stay f32 until after the cross-shard merge"))
    return findings


def check_double_dequant(traced, target: str = "") -> List[Finding]:
    findings: List[Finding] = []
    for j in walk_jaxprs(traced):
        widened: Dict[int, int] = {}
        for eqn in j.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            for var in eqn.invars:
                dt = _dtype_of(var)
                if dt == np.int8 and not isinstance(
                        getattr(var, "val", None), (int, np.generic)):
                    widened[id(var)] = widened.get(id(var), 0) + 1
        for n_conv in widened.values():
            if n_conv > 1:
                findings.append(Finding(
                    "double-dequant", target,
                    f"one int8 value widened by {n_conv} separate "
                    f"convert_element_type equations in a single jaxpr — "
                    f"the double-dequant bug shape (scale applied twice)"))
    return findings


def check_shard_map_reduction(traced, target: str = "") -> List[Finding]:
    """A shard_map with sharded inputs, replicated outputs, and no
    collective inside leaks unreduced partials. Param layout differs
    across jax versions — every access is defensive; regions we cannot
    interpret are skipped, not flagged."""
    findings: List[Finding] = []
    for eqn in iter_eqns(traced):
        if eqn.primitive.name != "shard_map":
            continue
        body = eqn.params.get("jaxpr")
        if body is None:
            continue
        names_in = eqn.params.get("in_names", eqn.params.get("in_specs"))
        names_out = eqn.params.get("out_names", eqn.params.get("out_specs"))
        if names_in is None or names_out is None:
            continue

        def _mapped(spec) -> Optional[bool]:
            if isinstance(spec, dict):                   # {axis_pos: names}
                return bool(spec)
            try:                                         # PartitionSpec-like
                return any(x is not None for x in tuple(spec))
            except TypeError:
                return None
        ins = [_mapped(s) for s in names_in]
        outs = [_mapped(s) for s in names_out]
        if any(i for i in ins if i) and outs \
                and all(o is False for o in outs):
            has_collective = any(
                e.primitive.name in _COLLECTIVES for e in iter_eqns(body))
            if not has_collective:
                findings.append(Finding(
                    "shard-map-reduction", target,
                    "shard_map region consumes sharded inputs, emits only "
                    "replicated outputs, and contains no collective — "
                    "unreduced per-shard partials escape"))
    return findings


# ---------------------------------------------------------------------- #
# Decode write ownership (numeric probe)
# ---------------------------------------------------------------------- #
def check_write_ownership(lay, target: str = "") -> List[Finding]:
    """Probe the sharded decode write routing over every cache position
    and shard index: a shard may write its owned slot's physical page or
    the null page 0 — never another shard's storage, never an inactive
    row's page."""
    import jax.numpy as jnp

    from repro.serve.engine import sharded_write_target

    findings: List[Finding] = []
    npp_s = lay.pages_per_shard
    T = lay.n_sink + lay.ring_cap + 5
    t_vec = jnp.arange(T, dtype=jnp.int32)
    active_np = (np.arange(T) % 4) != 3          # mix of live/dead rows
    active = jnp.asarray(active_np)
    table_np = 1 + np.arange(T * npp_s).reshape(T, npp_s)
    for idx in range(lay.shards):
        own_table = table_np + idx * T * npp_s
        keep, local_slot, phys, off = (
            np.asarray(a) for a in sharded_write_target(
                lay, jnp.asarray(own_table, jnp.int32), t_vec, active, idx))
        slot = np.asarray(lay.slot(t_vec))
        owner = np.asarray(lay.slot_owner(slot))
        for r in range(T):
            owned = bool(active_np[r]) and int(owner[r]) == idx
            if not owned:
                if phys[r] != 0:
                    findings.append(Finding(
                        "write-ownership", target,
                        f"shard {idx} writes physical page {int(phys[r])} "
                        f"for position {r} it does not own (owner "
                        f"{int(owner[r])}, active={bool(active_np[r])}) — "
                        f"non-owner writes must route to null page 0"))
                continue
            want = int(own_table[r, int(local_slot[r]) // lay.page])
            if int(phys[r]) != want or int(off[r]) != \
                    int(local_slot[r]) % lay.page:
                findings.append(Finding(
                    "write-ownership", target,
                    f"shard {idx} position {r}: write lands on page "
                    f"{int(phys[r])} offset {int(off[r])}, expected its "
                    f"own page {want} offset "
                    f"{int(local_slot[r]) % lay.page}"))

    # Unsharded twin: inactive rows must hit the null page.
    table = jnp.asarray(1 + np.arange(
        T * lay.pages_per_req).reshape(T, lay.pages_per_req), jnp.int32)
    phys, off = (np.asarray(a) for a in
                 lay.write_target(table, t_vec, keep=active))
    if (phys[~active_np] != 0).any():
        r = int(np.nonzero((phys != 0) & ~active_np)[0][0])
        findings.append(Finding(
            "write-ownership", target,
            f"inactive row {r} writes physical page {int(phys[r])}, "
            f"expected null page 0"))
    return findings


# ---------------------------------------------------------------------- #
# VMEM budget estimates
# ---------------------------------------------------------------------- #
def _block_bytes(*shape: int, itemsize: int = 4) -> int:
    """VMEM bytes of one block as Mosaic lays it out: the minor dim padded
    to LANES, the second-minor to the dtype's sublane tile (8 rows of 32
    bits: 8 f32, 16 bf16, 32 int8), leading dims whole."""
    *lead, rows, cols = (1,) * max(0, 2 - len(shape)) + shape
    sub = 8 * max(1, 4 // itemsize)
    n = (-(-rows // sub) * sub) * (-(-cols // LANES) * LANES) * itemsize
    for d in lead:
        n *= d
    return n


def attention_vmem_bytes(block_q: int, block_k: int, d: int,
                         dtype_bytes: int = 4) -> Dict[str, int]:
    """Resident bytes per grid step of each training launch, from the
    kernels' BlockSpecs: pipelined in/out blocks double-buffered, f32
    scratch once. Positions ride as (Bq, 1) columns / (1, Bk) rows, row
    stats as (1, Bq) rows, and lane-replicated (B, LANES) stat scratch."""
    bq, bk, t = block_q, block_k, dtype_bytes
    col = lambda n: _block_bytes(n, 1)          # noqa: E731  int32 column
    row = lambda n: _block_bytes(1, n)          # noqa: E731  int32/f32 row
    fwd = (2 * (col(bq) + row(bk) + 2 * _block_bytes(bq, d, itemsize=t)
                + 2 * _block_bytes(bk, d, itemsize=t) + 2 * row(bq))
           + _block_bytes(bq, d) + 2 * _block_bytes(bq, LANES))
    dq = (2 * (col(bq) + row(bk) + 3 * _block_bytes(bq, d, itemsize=t)
               + 2 * _block_bytes(bk, d, itemsize=t) + 3 * row(bq))
          + _block_bytes(bq, d) + 3 * _block_bytes(bq, LANES))
    dkv = (2 * (col(bk) + row(bq) + 2 * _block_bytes(bq, d, itemsize=t)
                + 2 * _block_bytes(bk, d, itemsize=t) + 3 * row(bq)
                + 2 * _block_bytes(bk, d))
           + 2 * _block_bytes(bk, d))
    return {"forward": fwd, "backward_dq": dq, "backward_dkv": dkv}


def decode_vmem_bytes(rep: int, head_dim: int, block_s: int, n_kv: int,
                      dtype_bytes: int, slab_bytes: int) -> int:
    """Paged ragged decode, one grid step = one page tile of all ``n_kv``
    heads: q/out (Hkv, rep, hd) at ``dtype_bytes``, k/v slab tiles
    (bs, Hkv, hd) at ``slab_bytes`` (1 for the int8 slab), a (1, bs)
    position row, the LANES-wide (m, l) stat blocks and the (8, LANES)
    page-max block (double-buffered), plus f32 scratch (acc +
    lane-replicated m, l per head)."""
    hv, t = n_kv, dtype_bytes
    pipelined = (2 * _block_bytes(hv, rep, head_dim, itemsize=t)
                 + 2 * _block_bytes(block_s, hv, head_dim,
                                    itemsize=slab_bytes)
                 + _block_bytes(1, block_s)
                 + 2 * _block_bytes(hv, rep, LANES)
                 + _block_bytes(8, LANES))
    scratch = (_block_bytes(hv, rep, head_dim)
               + 2 * _block_bytes(hv, rep, LANES))
    return 2 * pipelined + scratch


# Paged decode at smollm-135m serving shapes (9/3 heads, hd 64, 8-token
# pages): the full-precision slab, and the int8 slab with bf16 q/out that
# serving deploys.
SERVING_DECODE = {
    "paged_decode": dict(rep=3, head_dim=64, block_s=8, n_kv=3,
                         dtype_bytes=4, slab_bytes=4),
    "paged_decode_int8": dict(rep=3, head_dim=64, block_s=8, n_kv=3,
                              dtype_bytes=2, slab_bytes=1),
}


def check_vmem(plan, d: int = 64, dtype_bytes: int = 4,
               target: str = "", decode: Optional[Dict[str, dict]] = None,
               budget: int = VMEM_BUDGET) -> List[Finding]:
    """Flag any launch whose resident blocks exceed ``budget``: the three
    training launches of ``plan`` at ``dtype_bytes``, plus one paged
    decode launch per entry of ``decode`` (name -> the keyword arguments
    of :func:`decode_vmem_bytes`)."""
    findings: List[Finding] = []
    est = attention_vmem_bytes(plan.block_q, plan.block_k, d, dtype_bytes)
    for name, spec in (decode or {}).items():
        est[name] = decode_vmem_bytes(**spec)
    for name, b in est.items():
        if b > budget:
            findings.append(Finding(
                "vmem-budget", target,
                f"{name} launch holds ~{b / 2 ** 20:.1f} MiB resident "
                f"(blocks x dtype), over the {budget / 2 ** 20:.0f} MiB "
                f"VMEM budget"))
    return findings


# ---------------------------------------------------------------------- #
# Entry-point tracing drivers
# ---------------------------------------------------------------------- #
def trace_dkv_scatter(pattern, n: int, block_q: int, block_k: int):
    """Jaxpr of the runtime dK/dV scatter twin over a real plan's tables."""
    import jax
    import jax.numpy as jnp

    from repro.core.blockwise import table_dkv_scatter_scan
    from repro.core.scheduler import schedule

    sched = schedule(pattern, n)
    plan = sched.plan(block_q, block_k)
    pos = plan.positions_padded()
    pos_q = jnp.asarray(pos.reshape(plan.nq, block_q))
    pos_k = jnp.asarray(pos.reshape(plan.nkb, block_k))
    d = 16
    z = jnp.zeros((1, plan.n_pad, d), jnp.float32)
    r = jnp.zeros((1, plan.n_pad), jnp.float32)
    return jax.make_jaxpr(
        lambda dout, delta, m, l, q, k, v, kvb, fl: table_dkv_scatter_scan(
            dout, delta, m, l, q, k, v, pos_q, pos_k, kvb, fl, sched, 1.0)
    )(z, r, r, r, z, z, z, jnp.asarray(plan.kv_blocks),
      jnp.asarray(plan.flags))


def trace_masked_psum_merge():
    """Jaxpr of the cross-shard merge under a 1-device-mesh shard_map,
    with a bf16 ``out`` operand (the merge must cast, then psum f32)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as Pspec
    from repro.dist.sharded_plan import masked_psum_merge
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("seq",), devices=jax.devices()[:1])
    f = jax.shard_map(
        lambda o, m, l: masked_psum_merge(o, m, l, "seq"),
        mesh=mesh, in_specs=(Pspec("seq"), Pspec("seq"), Pspec("seq")),
        out_specs=Pspec("seq"), check_vma=False)
    o = jnp.zeros((1, 4, 8), jnp.bfloat16)
    s = jnp.zeros((1, 4), jnp.float32)
    return jax.make_jaxpr(f)(o, s, s)


def trace_engine_decode(eng, params):
    """Jaxpr of an engine's ragged-decode step from its live state (the
    same trace the observability zero-cost gate compares)."""
    import jax
    import jax.numpy as jnp

    R = eng.ccfg.max_batch
    z = jnp.zeros(R, jnp.int32)
    return jax.make_jaxpr(eng._decode_fn)(
        params, eng.slabs, eng.page_tables.copy(), eng.slot_pos,
        z, z, jnp.zeros(R, bool))


def lint_traced(traced, target: str = "") -> List[Finding]:
    """All effect checks that apply to an arbitrary traced jaxpr."""
    return (check_scatter_modes(traced, target)
            + check_psum_dtype(traced, target)
            + check_double_dequant(traced, target)
            + check_shard_map_reduction(traced, target))
