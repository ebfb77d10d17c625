"""Slot-granular ref-access IR for Pallas kernel jaxprs.

The symbolic half of ``repro.analysis``: where :mod:`jaxpr_lint` walks a
kernel jaxpr *syntactically* (ref-base granularity, no index values), this
module runs an abstract interpretation of the kernel over its whole grid
and extracts a typed access record per ``get``/``swap``/``dma_start``/
``dma_wait`` — which ref, which *slot* (the per-dimension index footprint),
under which ``pl.when`` guards, at which grid points.

The abstract domain is deliberately concrete: every scalar the Segment
kernels compute is a function of the grid coordinates and the
scalar-prefetch schedule arrays, and the schedule arrays are plan-time
constants (already certified by :mod:`repro.analysis.invariants`).  So the
interpreter carries each scalar as a *vector over all grid points* — exact
constant propagation per point, with ``TOP`` (``None``) for anything
data-dependent (tensor values, loop carries).  Downstream passes
(:mod:`ranges`, :mod:`races`, :mod:`budget`) reduce these vectors to
interval proofs, per-slot hazard simulations, and byte budgets.

Fixes the documented ref-base false negative of the syntactic linter: a
``(depth, …)`` ring buffer is no longer one opaque base — each access
carries its resolved slot per grid point.

Entry points:

* :func:`kernel_ir_from_eqn` — build a :class:`KernelIR` from one traced
  ``pallas_call`` equation plus the resolved scalar-prefetch arrays;
* :func:`find_kernel_invocations` — walk a host-level jaxpr, resolving the
  scalar-prefetch operands of every reachable ``pallas_call`` from the
  trace's constants (works through ``pjit`` / ``custom_vjp`` nesting);
* :func:`trace_kernel_irs` — trace a callable and return one IR per
  kernel.

Imports: jax + numpy only; this module must stay importable without the
planner (layering mirror of :mod:`jaxpr_lint`).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import tree_util
from jax.experimental import pallas as pl

TOP = None          #: unknown abstract value (data-dependent / loop-carried)

#: hard cap on grid points per analyzed kernel — the interpreter is O(grid)
#: per scalar; analysis targets the CI-sized variant grid, not production
#: shapes (the schedule proofs are shape-generic through invariants.py).
MAX_GRID_POINTS = 1 << 20

READ_KINDS = ("read", "dma_src")
WRITE_KINDS = ("write", "dma_dst")


@dataclasses.dataclass(frozen=True)
class RefInfo:
    """One kernel operand/scratch ref, canonicalized."""

    role: str                    # prefetch | input | output | scratch | local
    index: int                   # position within the role
    name: str                    # e.g. "in0", "out0", "scratch2"
    shape: Tuple[int, ...]       # backing array shape (full, not block)
    dtype: str
    memspace: str                # smem | any | vmem | semaphore | blocked
    block_shape: Optional[Tuple[int, ...]] = None   # BlockSpec window


@dataclasses.dataclass(frozen=True)
class Dim:
    """Access footprint along one ref dimension.

    ``start`` is an int (static), a ``(G,)`` int64 vector (one value per
    grid point), or ``TOP``.  ``size`` is an int or ``TOP``.  ``full``
    marks a static whole-extent slice.
    """

    start: object
    size: object
    full: bool


@dataclasses.dataclass
class Access:
    """One ref access with its per-grid-point footprint and guard."""

    ref: RefInfo
    kind: str                        # read | write | dma_src | dma_dst | dma_wait
    dims: Tuple[Dim, ...]
    extent: Tuple[int, ...]          # ref shape the indexer was taken against
    mask: Optional[np.ndarray]       # bool (G,) guard; None when not certain
    certain: bool
    seq: int                         # kernel program order
    sem: Optional[RefInfo] = None    # owning DMA semaphore (dma_* kinds)
    sem_slot: object = TOP           # semaphore slot: int | (G,) vector | TOP
    in_loop: bool = False            # recorded inside scan/while (multiplicity
    #                                  and index not grid-resolved)

    def slot(self) -> object:
        """Leading-dimension slot of this footprint: int, (G,) vector,
        ``"all"`` for a full leading slice, or TOP."""
        if not self.dims:
            return "all"
        d = self.dims[0]
        if d.full:
            return "all"
        if d.size == 1 and d.start is not TOP:
            return d.start
        return TOP

    def rest_full(self) -> bool:
        return all(d.full for d in self.dims[1:])


@dataclasses.dataclass
class KernelIR:
    """The access IR of one traced kernel over its concrete grid."""

    name: str
    grid: Tuple[int, ...]
    semantics: Tuple[str, ...]           # per-axis dimension_semantics
    parallel_axes: Tuple[int, ...]
    coords: Tuple[np.ndarray, ...]       # (G,) int64 per grid axis, row-major
    refs: List[RefInfo]
    accesses: List[Access]
    #: blocked (non-ANY) input/output refs → per-axis block coords over the
    #: grid (int | (G,) vector | TOP), from the BlockSpec index maps
    block_coords: Dict[str, Tuple[object, ...]]
    #: same refs → per-axis number of blocks (bounds for the coords)
    block_bounds: Dict[str, Tuple[int, ...]]
    scalars: Dict[str, Optional[np.ndarray]]   # prefetch name → values

    @property
    def n_points(self) -> int:
        return int(np.prod(self.grid)) if self.grid else 1

    def point(self, p: int) -> Tuple[int, ...]:
        """Grid coordinates of flattened point ``p`` (row-major)."""
        return tuple(int(c[p]) for c in self.coords)

    @property
    def sequential_axes(self) -> Tuple[int, ...]:
        """Grid axes *not* declared parallel, outermost first — the axes
        Mosaic executes in program order within one parallel iteration."""
        return tuple(ax for ax in range(len(self.grid))
                     if ax not in self.parallel_axes)

    def may_mask(self, a: Access) -> np.ndarray:
        """Guard as a may-execute mask (unknown guards → everywhere)."""
        if a.certain and a.mask is not None:
            return a.mask
        return np.ones(self.n_points, bool)

    def must_mask(self, a: Access) -> np.ndarray:
        """Guard as a must-execute mask (unknown guards → nowhere)."""
        if a.certain and a.mask is not None:
            return a.mask
        return np.zeros(self.n_points, bool)


# ---------------------------------------------------------------------------
# small helpers shared with the syntactic linter (duplicated to keep this
# module import-independent of jaxpr_lint)
# ---------------------------------------------------------------------------


def _is_sem_aval(aval) -> bool:
    return aval is not None and "semaphore" in str(aval).lower()


def _is_ref_aval(aval) -> bool:
    return aval is not None and "Ref" in type(aval).__name__


def _is_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")


def _memspace(aval) -> str:
    s = str(aval).lower()
    if "semaphore" in s:
        return "semaphore"
    for name in ("smem", "vmem", "any"):
        if f"<{name}>" in s:
            return name
    return "blocked"        # MemRef<None>{…}: a BlockSpec-windowed operand


def _subjaxprs(eqn):
    """Yield (jaxpr, consts) for every sub-jaxpr in one eqn's params."""
    for pv in eqn.params.values():
        vals = pv if isinstance(pv, (tuple, list)) else (pv,)
        for v in vals:
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner, tuple(getattr(v, "consts", ()))
            elif hasattr(v, "eqns"):
                yield v, ()


# ---------------------------------------------------------------------------
# scalar op table (vectorized over grid points)
# ---------------------------------------------------------------------------


def _trunc_div(a, b):
    a = np.asarray(a)
    b = np.asarray(b)
    if np.issubdtype(np.result_type(a, b), np.integer):
        q = np.abs(a) // np.maximum(np.abs(b), 1)
        return (np.sign(a) * np.sign(b) * q).astype(np.int64)
    return a / b


def _trunc_rem(a, b):
    # lax.rem is C-style (truncated) remainder; np.fmod matches
    return np.fmod(np.asarray(a), np.asarray(b))


_BINOPS = {
    "add": np.add, "sub": np.subtract, "mul": np.multiply,
    "max": np.maximum, "min": np.minimum,
    "div": _trunc_div, "rem": _trunc_rem,
    "and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor,
    "eq": np.equal, "ne": np.not_equal, "lt": np.less, "le": np.less_equal,
    "gt": np.greater, "ge": np.greater_equal,
    "shift_left": np.left_shift,
    "shift_right_logical": np.right_shift,
    "shift_right_arithmetic": np.right_shift,
}

_UNOPS = {
    "neg": np.negative, "not": np.bitwise_not, "sign": np.sign,
    "abs": np.abs, "floor": np.floor, "ceil": np.ceil,
    "stop_gradient": lambda v: v, "copy": lambda v: v,
}

_CALL_PRIMS = ("pjit", "closed_call", "core_call", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
               "checkpoint", "custom_lin")


class _Interp:
    """Vectorized abstract interpreter over one kernel jaxpr."""

    def __init__(self, ir: KernelIR, refmap: Dict[object, RefInfo]):
        self.ir = ir
        self.G = ir.n_points
        self.env: Dict[object, object] = {}
        self.alias: Dict[object, object] = {}
        self.refmap = refmap            # canonical var -> RefInfo
        self.seq = 0

    # -- value plumbing -----------------------------------------------------

    def read(self, v):
        if hasattr(v, "val"):                      # Literal
            val = np.asarray(v.val)
            return val if val.ndim == 0 else TOP
        return self.env.get(v, TOP)

    def bind(self, v, val) -> None:
        if val is not TOP:
            self.env[v] = val

    def canon(self, v):
        while v in self.alias:
            v = self.alias[v]
        return v

    def ref_of(self, v) -> Optional[RefInfo]:
        return self.refmap.get(self.canon(v))

    def _alias_refs(self, sub_invars, operands) -> None:
        for sv, ov in zip(sub_invars, operands):
            if _is_var(ov) and _is_ref_aval(getattr(ov, "aval", None)):
                self.alias[sv] = self.canon(ov)
            else:
                # Vars and Literals alike (jnp lowers e.g. ``%`` to a pjit
                # whose remainder jaxpr takes literal operands — dropping
                # them would poison every downstream slot with TOP)
                self.bind(sv, self.read(ov))

    # -- indexer decoding ---------------------------------------------------

    def _decode_indexer(self, transforms, aval):
        """(dims, extent) from a ref transform tuple (NDIndexer pytree)."""
        nd = None
        for t in (transforms or ()):
            if type(t).__name__ == "NDIndexer":
                nd = t
                break
        if nd is None:
            shape = tuple(getattr(aval, "shape", ()) or ())
            dims = tuple(Dim(0, s, True) for s in shape)
            return dims, shape
        extent = tuple(int(s) for s in nd.shape)
        dims = []
        for d, idx in enumerate(nd.indices):
            tname = type(idx).__name__
            if tname == "Slice":
                start = idx.start
                if _is_var(start) or hasattr(start, "val"):
                    start = self.read(start)
                size = idx.size
                if hasattr(size, "val"):
                    size = int(np.asarray(size.val))
                elif _is_var(size):
                    size = TOP
                stride = getattr(idx, "stride", 1)
                if _is_var(stride) or (size is not TOP and stride != 1):
                    # conservative span for strided slices
                    size = TOP if size is TOP else (size - 1) * stride + 1
                dims.append(Dim(_norm_start(start, self.G),
                                size if size is TOP else int(size),
                                _is_static_full(start, size, extent[d])))
            elif isinstance(idx, (int, np.integer)):
                dims.append(Dim(int(idx), 1, extent[d] == 1))
            elif getattr(getattr(idx, "aval", None), "shape",
                         None) == ():       # scalar Var or Literal
                val = self.read(idx)
                if isinstance(val, np.ndarray) and val.ndim == 0:
                    val = int(val)
                if isinstance(val, (int, np.integer)):
                    dims.append(Dim(int(val), 1,
                                    extent[d] == 1 and int(val) == 0))
                else:
                    dims.append(Dim(_norm_start(val, self.G), 1, False))
            else:                       # array indexer / anything else
                dims.append(Dim(TOP, TOP, False))
        return tuple(dims), extent

    # -- access recording ---------------------------------------------------

    def record(self, ref_var, transforms, kind, mask, certain, in_loop,
               sem=None, sem_slot=TOP) -> Optional[Access]:
        ref = self.ref_of(ref_var)
        if ref is None:
            aval = getattr(ref_var, "aval", None)
            ref = RefInfo("local", len(self.refmap), f"local{len(self.refmap)}",
                          tuple(getattr(aval, "shape", ()) or ()),
                          str(getattr(aval, "dtype", "?")), _memspace(aval))
            self.refmap[self.canon(ref_var)] = ref
        dims, extent = self._decode_indexer(transforms,
                                            getattr(ref_var, "aval", None))
        acc = Access(ref=ref, kind=kind, dims=dims, extent=extent,
                     mask=mask if certain else None, certain=certain,
                     seq=self.seq, sem=sem, sem_slot=sem_slot,
                     in_loop=in_loop)
        self.seq += 1
        self.ir.accesses.append(acc)
        return acc

    # -- primitive handlers -------------------------------------------------

    def _scalar_lookup(self, ref: RefInfo, dims) -> object:
        """Value of a scalar ``get`` from a resolved prefetch array."""
        arr = self.ir.scalars.get(ref.name)
        if arr is None or len(dims) != 1 or dims[0].size != 1 \
                or dims[0].start is TOP:
            return TOP
        idx = np.clip(dims[0].start, 0, len(arr) - 1)
        return np.asarray(arr)[idx]

    def _get(self, eqn, mask, certain, in_loop) -> None:
        tree = eqn.params.get("tree")
        transforms = _unflatten_transforms(tree, eqn.invars[1:])
        acc = self.record(eqn.invars[0], transforms, "read", mask, certain,
                          in_loop)
        out = eqn.outvars[0]
        if getattr(out.aval, "shape", None) == () and acc is not None \
                and acc.ref.role == "prefetch":
            self.bind(out, self._scalar_lookup(acc.ref, acc.dims))

    def _swap(self, eqn, mask, certain, in_loop) -> None:
        transforms = _unflatten_transforms(eqn.params.get("tree"),
                                           eqn.invars[2:])
        self.record(eqn.invars[0], transforms, "write", mask, certain,
                    in_loop)

    def _addupdate(self, eqn, mask, certain, in_loop) -> None:
        transforms = _unflatten_transforms(eqn.params.get("tree"),
                                           eqn.invars[2:])
        self.record(eqn.invars[0], transforms, "read", mask, certain, in_loop)
        self.record(eqn.invars[0], transforms, "write", mask, certain,
                    in_loop)

    def _dma_pairs(self, eqn):
        """[(ref_var, transforms)] parsed from a dma_start/dma_wait tree."""
        tree = eqn.params.get("tree")
        if tree is None:
            return []
        try:
            flat = tree_util.tree_unflatten(tree, eqn.invars)
        except Exception:
            return []
        items = list(flat) if isinstance(flat, (tuple, list)) else [flat]
        pairs = []
        i = 0
        while i < len(items):
            v = items[i]
            if _is_var(v) and _is_ref_aval(getattr(v, "aval", None)):
                transforms = ()
                if i + 1 < len(items) and isinstance(items[i + 1],
                                                     (tuple, list)):
                    transforms = tuple(items[i + 1])
                    i += 1
                pairs.append((v, transforms))
            i += 1
        return pairs

    def _dma(self, eqn, kind, mask, certain, in_loop) -> None:
        pairs = self._dma_pairs(eqn)
        sem_pair = None
        refs = []
        for v, tr in pairs:
            if _is_sem_aval(getattr(v, "aval", None)):
                if sem_pair is None:
                    sem_pair = (v, tr)
            else:
                refs.append((v, tr))
        sem = sem_slot = None
        if sem_pair is not None:
            sem = self.ref_of(sem_pair[0])
            sdims, _ = self._decode_indexer(sem_pair[1],
                                            getattr(sem_pair[0], "aval", None))
            sem_slot = sdims[0].start if (sdims and sdims[0].size == 1) \
                else ("all" if sdims and sdims[0].full else TOP)
        if kind == "dma_start":
            if len(refs) >= 2:
                self.record(refs[0][0], refs[0][1], "dma_src", mask, certain,
                            in_loop)
            if refs:
                v, tr = refs[-1]
                self.record(v, tr, "dma_dst", mask, certain, in_loop,
                            sem=sem, sem_slot=sem_slot)
        else:                           # dma_wait: attribute to the dst ref
            if refs:
                v, tr = refs[-1]
                self.record(v, tr, "dma_wait", mask, certain, in_loop,
                            sem=sem, sem_slot=sem_slot)

    def _cond(self, eqn, mask, certain, in_loop) -> None:
        pred = self.read(eqn.invars[0])
        branches = eqn.params.get("branches", ())
        branch_vals = []
        for k, br in enumerate(branches):
            sub = getattr(br, "jaxpr", br)
            self._alias_refs(sub.invars, eqn.invars[1:])
            if pred is TOP:
                sub_mask, sub_certain = mask, False
            else:
                pv = np.broadcast_to(np.asarray(pred), (self.G,))
                sub_mask = mask & (pv.astype(np.int64) == k)
                sub_certain = certain
            self.walk(sub, sub_mask, sub_certain, in_loop)
            branch_vals.append([self.read(v) for v in sub.outvars])
        # merge branch outputs where every branch yields a known scalar
        for i, out in enumerate(eqn.outvars):
            if getattr(out.aval, "shape", None) != () or pred is TOP:
                continue
            vals = [bv[i] if i < len(bv) else TOP for bv in branch_vals]
            if any(v is TOP for v in vals):
                continue
            pv = np.broadcast_to(np.asarray(pred), (self.G,)).astype(np.int64)
            sel = np.select([pv == k for k in range(len(vals))],
                            [np.broadcast_to(np.asarray(v), (self.G,))
                             for v in vals],
                            default=np.broadcast_to(np.asarray(vals[-1]),
                                                    (self.G,)))
            self.bind(out, sel)

    def _loop(self, eqn, mask, in_loop) -> None:
        """scan / while: walk bodies once with TOP carries (accesses are
        recorded with unknown multiplicity → guard marked uncertain)."""
        for sub, consts in _subjaxprs(eqn):
            # bind what aligns positionally (scan consts lead the invars)
            for sv, ov in zip(sub.invars, eqn.invars):
                if _is_var(ov) and _is_ref_aval(getattr(ov, "aval", None)):
                    self.alias[sv] = self.canon(ov)
            self.walk(sub, mask, False, True)

    def _call(self, eqn, mask, certain, in_loop) -> bool:
        for sub, consts in _subjaxprs(eqn):
            if len(sub.invars) != len(eqn.invars):
                continue
            self._alias_refs(sub.invars, eqn.invars)
            self.walk(sub, mask, certain, in_loop)
            for ov, sv in zip(eqn.outvars, sub.outvars):
                if getattr(ov.aval, "shape", None) == ():
                    self.bind(ov, self.read(sv))
            return True
        return False

    # -- the walk -----------------------------------------------------------

    def walk(self, jaxpr, mask, certain, in_loop=False) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "cond":
                self._cond(eqn, mask, certain, in_loop)
            elif prim == "get":
                self._get(eqn, mask, certain, in_loop)
            elif prim == "swap":
                self._swap(eqn, mask, certain, in_loop)
            elif prim == "addupdate":
                self._addupdate(eqn, mask, certain, in_loop)
            elif prim in ("dma_start", "dma_wait"):
                self._dma(eqn, prim, mask, certain, in_loop)
            elif prim == "program_id":
                self.bind(eqn.outvars[0],
                          self.ir.coords[eqn.params["axis"]])
            elif prim == "num_programs":
                self.bind(eqn.outvars[0],
                          np.int64(self.ir.grid[eqn.params["axis"]]))
            elif prim in ("scan", "while"):
                self._loop(eqn, mask, in_loop)
            elif prim == "convert_element_type":
                v = self.read(eqn.invars[0])
                if v is not TOP:
                    dt = np.dtype(eqn.params.get("new_dtype", "int64"))
                    self.bind(eqn.outvars[0], np.asarray(v).astype(dt))
            elif prim == "select_n":
                self._select_n(eqn)
            elif prim == "integer_pow":
                v = self.read(eqn.invars[0])
                if v is not TOP:
                    self.bind(eqn.outvars[0],
                              np.asarray(v) ** eqn.params["y"])
            elif prim in _BINOPS and self._scalar_out(eqn):
                a, b = (self.read(v) for v in eqn.invars[:2])
                if a is not TOP and b is not TOP:
                    self.bind(eqn.outvars[0], _BINOPS[prim](a, b))
            elif prim in _UNOPS and self._scalar_out(eqn):
                a = self.read(eqn.invars[0])
                if a is not TOP:
                    self.bind(eqn.outvars[0], _UNOPS[prim](a))
            elif prim in ("broadcast_in_dim", "reshape", "squeeze",
                          "expand_dims"):
                if self._scalar_out(eqn):
                    self.bind(eqn.outvars[0], self.read(eqn.invars[0]))
            elif prim in _CALL_PRIMS:
                self._call(eqn, mask, certain, in_loop)
            else:
                # unknown primitive: outputs stay TOP; still walk reachable
                # sub-jaxprs so no access goes unrecorded (conservatively
                # uncertain — we cannot interpret the calling convention)
                if not self._call(eqn, mask, certain, in_loop):
                    for sub, _ in _subjaxprs(eqn):
                        self.walk(sub, mask, False, in_loop)

    def _scalar_out(self, eqn) -> bool:
        return (len(eqn.outvars) == 1
                and getattr(eqn.outvars[0].aval, "shape", None) == ())

    def _select_n(self, eqn) -> None:
        if not self._scalar_out(eqn):
            return
        vals = [self.read(v) for v in eqn.invars]
        if any(v is TOP for v in vals):
            return
        pred, cases = vals[0], vals[1:]
        pv = np.broadcast_to(np.asarray(pred), (self.G,)).astype(np.int64)
        out = np.select([pv == k for k in range(len(cases))],
                        [np.broadcast_to(np.asarray(c), (self.G,))
                         for c in cases],
                        default=np.broadcast_to(np.asarray(cases[-1]),
                                                (self.G,)))
        self.bind(eqn.outvars[0], out)


def _norm_start(start, G):
    if start is TOP:
        return TOP
    arr = np.asarray(start)
    if arr.ndim == 0:
        return int(arr)
    return np.broadcast_to(arr, (G,)).astype(np.int64)


def _is_static_full(start, size, extent) -> bool:
    return (isinstance(start, (int, np.integer)) and int(start) == 0
            and size is not TOP and int(size) == int(extent))


def _block_dim(b) -> int:
    """One BlockSpec window dimension as an int: ``Blocked``, ``Element`` and
    ``BoundedSlice`` carry ``block_size``; ``Squeezed`` is a unit dimension
    the kernel never sees."""
    return 1 if isinstance(b, pl.Squeezed) else int(b.block_size)


def _unflatten_transforms(tree, leaves):
    if tree is None:
        return ()
    try:
        flat = tree_util.tree_unflatten(tree, list(leaves))
    except Exception:
        return ()
    return tuple(flat) if isinstance(flat, (tuple, list)) else (flat,)


# ---------------------------------------------------------------------------
# IR construction from a traced pallas_call equation
# ---------------------------------------------------------------------------


def _dimension_semantics(eqn, n_axes: int) -> Tuple[str, ...]:
    cp = eqn.params.get("compiler_params") or {}
    sem = getattr(cp.get("mosaic_tpu"), "dimension_semantics", None)
    if sem is None:
        return ("arbitrary",) * n_axes
    return tuple(str(s) for s in sem)


def kernel_ir_from_eqn(eqn, name: str = "<kernel>",
                       scalars: Optional[Sequence] = None) -> KernelIR:
    """Build the access IR of one traced ``pallas_call`` equation.

    ``scalars`` supplies the values of the scalar-prefetch operands in
    kernel-argument order (numpy arrays, or None per entry when unknown);
    :func:`find_kernel_invocations` resolves them automatically from the
    host trace.
    """
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid) or (1,)
    G = int(np.prod(grid))
    if G > MAX_GRID_POINTS:
        raise ValueError(
            f"kernel {name!r}: grid {grid} has {G} points, beyond the "
            f"analyzer cap ({MAX_GRID_POINTS}); analyze a CI-sized variant "
            f"of the kernel instead (the proofs are schedule-generic)")
    kj = eqn.params["jaxpr"]
    kj = getattr(kj, "jaxpr", kj)
    n_idx = gm.num_index_operands
    n_in = gm.num_inputs
    n_out = gm.num_outputs
    semantics = _dimension_semantics(eqn, len(grid))
    if len(semantics) < len(grid):
        semantics = semantics + ("arbitrary",) * (len(grid) - len(semantics))
    coords = tuple(c.reshape(-1).astype(np.int64)
                   for c in np.indices(grid))

    scalar_vals: Dict[str, Optional[np.ndarray]] = {}
    refs: List[RefInfo] = []
    refmap: Dict[object, RefInfo] = {}
    bms = list(gm.block_mappings)
    for pos, var in enumerate(kj.invars):
        aval = getattr(var, "aval", None)
        shape = tuple(getattr(aval, "shape", ()) or ())
        dtype = str(getattr(aval, "dtype", "?"))
        if pos < n_idx:
            info = RefInfo("prefetch", pos, f"prefetch{pos}", shape, dtype,
                           _memspace(aval))
            val = None
            if scalars is not None and pos < len(scalars) \
                    and scalars[pos] is not None:
                val = np.asarray(scalars[pos])
            scalar_vals[info.name] = val
        elif pos < n_idx + n_in + n_out:
            io = pos - n_idx
            bm = bms[io] if io < len(bms) else None
            role = "input" if io < n_in else "output"
            idx = io if io < n_in else io - n_in
            block_shape = None
            array_shape = shape
            space = _memspace(aval)
            if bm is not None:
                block_shape = tuple(_block_dim(b) for b in bm.block_shape)
                array_shape = tuple(int(s) for s in bm.array_aval.shape)
                dtype = str(bm.array_aval.dtype)
                space = _memspace(bm.transformed_block_aval)
            info = RefInfo(role, idx, f"{'in' if role == 'input' else 'out'}"
                           f"{idx}", array_shape, dtype, space, block_shape)
        else:
            k = pos - n_idx - n_in - n_out
            info = RefInfo("scratch", k, f"scratch{k}", shape, dtype,
                           _memspace(aval))
        refs.append(info)
        refmap[var] = info

    ir = KernelIR(name=name, grid=grid, semantics=semantics,
                  parallel_axes=tuple(i for i, s in enumerate(semantics)
                                      if s == "parallel"),
                  coords=coords, refs=refs, accesses=[],
                  block_coords={}, block_bounds={}, scalars=scalar_vals)

    interp = _Interp(ir, refmap)

    # BlockSpec index maps: evaluate each blocked operand's block coords
    # over the grid (the index-map jaxprs read the prefetch refs, so run
    # them through the same interpreter — their SMEM reads are recorded and
    # range-checked like any kernel access)
    prefetch_refs = [refs[i] for i in range(n_idx)]
    for io, bm in enumerate(bms):
        info = refs[n_idx + io]
        if info.memspace == "any" or bm is None or info.block_shape is None:
            continue
        imap = getattr(bm, "index_map_jaxpr", None)
        if imap is None:
            continue
        sub = getattr(imap, "jaxpr", imap)
        for cv, c in zip(getattr(sub, "constvars", ()),
                         getattr(imap, "consts", ())):
            if np.ndim(c) == 0:
                interp.bind(cv, np.asarray(c))
        n_axes = len(grid)
        for v, c in zip(sub.invars[:n_axes], coords):
            interp.bind(v, c)
        for v, pr in zip(sub.invars[n_axes:], prefetch_refs):
            interp.refmap[interp.canon(v)] = pr
        interp.walk(sub, np.ones(G, bool), True)
        out_coords = tuple(interp.read(v) if _is_var(v)
                           else int(np.asarray(v.val)) for v in sub.outvars)
        ir.block_coords[info.name] = tuple(
            _norm_start(c, G) for c in out_coords)
        ir.block_bounds[info.name] = tuple(
            -(-a // max(b, 1)) for a, b in zip(info.shape, info.block_shape))

    interp.walk(kj, np.ones(G, bool), True)
    return ir


# ---------------------------------------------------------------------------
# host-level kernel discovery with scalar-prefetch resolution
# ---------------------------------------------------------------------------


def find_kernel_invocations(closed, args=()) -> List[Tuple[str, object, list]]:
    """Collect ``(name, eqn, scalar_values)`` for every reachable
    ``pallas_call`` in a host-level jaxpr.

    Scalar-prefetch operand values are resolved by propagating the trace's
    constants (and the concrete ``args``) through the host jaxpr — plan
    schedule arrays are closed-over constants, so this recovers them even
    under ``pjit`` / ``custom_vjp`` nesting (the grad trace).  Unresolvable
    operands come back as ``None`` entries (analysis degrades to TOP).
    """
    env: Dict[object, np.ndarray] = {}
    out: List[Tuple[str, object, list]] = []

    def rd(v):
        if hasattr(v, "val"):
            return np.asarray(v.val)
        return env.get(v)

    def walk(j):
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                gm = e.params.get("grid_mapping")
                n_idx = getattr(gm, "num_index_operands", 0)
                info = e.params.get("name_and_src_info")
                name = (getattr(info, "name", None)
                        or e.params.get("name") or "<pallas_call>")
                out.append((str(name), e, [rd(v) for v in e.invars[:n_idx]]))
                continue
            if e.primitive.name in ("convert_element_type", "copy",
                                    "device_put", "reshape",
                                    "broadcast_in_dim", "squeeze"):
                val = rd(e.invars[0])
                if val is not None and e.primitive.name in (
                        "convert_element_type", "copy", "device_put"):
                    env[e.outvars[0]] = val
                continue
            for sub, consts in _subjaxprs(e):
                for cv, c in zip(getattr(sub, "constvars", ()), consts):
                    if hasattr(c, "shape"):
                        env[cv] = np.asarray(c)
                if len(sub.invars) == len(e.invars):
                    for sv, ov in zip(sub.invars, e.invars):
                        val = rd(ov)
                        if val is not None:
                            env[sv] = val
                walk(sub)
                for ov, sv in zip(e.outvars, sub.outvars):
                    val = rd(sv)
                    if val is not None:
                        env[ov] = val

    jaxpr = getattr(closed, "jaxpr", closed)
    for v, c in zip(getattr(jaxpr, "constvars", ()),
                    getattr(closed, "consts", ())):
        if hasattr(c, "shape"):
            env[v] = np.asarray(c)
    flat_args = tree_util.tree_leaves(args)
    for v, a in zip(jaxpr.invars, flat_args):
        if hasattr(a, "shape"):
            env[v] = np.asarray(a)
    walk(jaxpr)
    return out


def trace_kernel_irs(fn, *args, label: Optional[str] = None,
                     **kwargs) -> List[KernelIR]:
    """Trace ``fn(*args, **kwargs)`` and build one :class:`KernelIR` per
    reachable Pallas kernel.  Raises ``ValueError`` when the trace holds no
    ``pallas_call`` (a vacuous analysis gate is a bug, not a pass)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    found = find_kernel_invocations(closed, args)
    if not found:
        raise ValueError(
            f"no pallas_call found while tracing "
            f"{label or getattr(fn, '__name__', fn)!r} — nothing to analyze")
    irs = []
    for name, eqn, scalars in found:
        irs.append(kernel_ir_from_eqn(
            eqn, name=f"{label}:{name}" if label else name, scalars=scalars))
    return irs
