"""Schedule/algorithm separation for the Pallas kernel stack.

Every kernel in ``repro.kernels`` computes a fixed function (the
*algorithm*); how that function is tiled over the grid, what dtype the MXU
products run in, the grid iteration order and where the accumulator lives
are the *schedule* (the SYS_ATL/Exo separation).  A :class:`Schedule` makes
those choices an explicit, serializable value that can be

  * passed to any public kernel wrapper (``ops.block_matmat(...,
    schedule=...)``) — ``schedule=None`` reproduces the old keyword-tile
    behavior bit-for-bit;
  * searched by the autotuner (:mod:`repro.tune.autotune`) and persisted
    per (kernel, shape bucket, device) in :mod:`repro.tune.cache`;
  * checked for *legality* before it ever reaches a ``pallas_call``:
    MXU sublane/lane multiples, per-kernel knob support, and a VMEM
    working-set model — so an illegal tile raises a one-line ValueError
    here instead of an opaque Pallas lowering failure.

:class:`KernelSpec` is the per-kernel contract: the default schedule (the
old hard-coded tiles), which schedule knobs the kernel supports, the VMEM
model, and the FLOPs/bytes models the roofline report uses.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

# TPU tiling floor for f32 operands: (sublane, lane) = (8, 128).  Sublane
# multiples are enforced always (they are also what keeps the interpret and
# compiled paths shape-compatible); lane multiples only matter once the
# kernel is actually lowered for the MXU, so interpret-mode schedules may
# relax them (the small-tile test schedules rely on this).
SUBLANE = 8
LANE = 128

# Per-grid-step VMEM working-set ceiling: half of Mosaic's default scoped
# VMEM on a v5e (16 MiB), the other half left to the compiler.  The fused
# kernels' models count their double-buffered input and output tiles and
# their scratch; the others count one buffer of each tile.
VMEM_BYTES = 8 * 1024 * 1024

GRID_ORDERS = ("row-major", "col-major")
ACCS = ("inplace", "scratch")
_DTYPE_NAMES = {None: None, "f32": "float32", "float32": "float32",
                "bf16": "bfloat16", "bfloat16": "bfloat16"}


class ScheduleError(ValueError):
    """An illegal schedule for a given kernel/shape (clear, pre-lowering)."""


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One point in a kernel's schedule space.

    ``None`` fields mean "inherit": the resolver fills them from the call
    site's keyword arguments (which carry the historical defaults), so a
    partial schedule like ``Schedule(compute_dtype="bf16")`` only overrides
    what it names.

    bm / bn:        row / column (reduction-side) tile edges.
    bd:             feature tile of the kernels with a feature grid axis
                    (``fused_rbf_matmat``): None = the default rule
                    (``kernels.fused_rbf_matmat.default_d_tile``).
    compute_dtype:  MXU product precision ("float32" | "bfloat16") for the
                    kernels that expose it; accumulation stays f32.
    grid_order:     "row-major" (default: last grid dim fastest) or
                    "col-major" (first fastest) — only legal for kernels
                    whose output tiles are written exactly once.
    acc:            accumulator placement for reducing kernels: "inplace"
                    (accumulate into the revisited output tile) or
                    "scratch" (f32 VMEM scratch, one output write at the
                    last reduction step).
    interpret:      force the Pallas interpreter (None = auto-detect:
                    compiled on TPU, interpreted elsewhere).
    """
    bm: Optional[int] = None
    bn: Optional[int] = None
    bd: Optional[int] = None
    compute_dtype: Optional[str] = None
    grid_order: str = "row-major"
    acc: str = "inplace"
    interpret: Optional[bool] = None

    def __post_init__(self):
        # normalize dtype aliases ("bf16"/"f32") at construction so equal
        # schedules compare equal regardless of how they were spelled
        cd = self.compute_dtype
        if cd is not None:
            cd = str(cd).lower()
            if cd not in _DTYPE_NAMES:
                raise ScheduleError(
                    f"schedule compute_dtype must be one of "
                    f"{sorted(k for k in _DTYPE_NAMES if k)}, got "
                    f"{self.compute_dtype!r}")
            object.__setattr__(self, "compute_dtype", _DTYPE_NAMES[cd])

    def replace(self, **kw) -> "Schedule":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        return {k: v for k, v in d.items() if v is not None}

    @classmethod
    def from_dict(cls, d: dict) -> "Schedule":
        fields = {f.name for f in dataclasses.fields(cls)}
        extra = set(d) - fields
        if extra:
            raise ScheduleError(
                f"unknown schedule field(s) {sorted(extra)}; "
                f"expected a subset of {sorted(fields)}")
        d = dict(d)
        if "compute_dtype" in d and d["compute_dtype"] is not None:
            spec = str(d["compute_dtype"]).lower()
            if spec not in _DTYPE_NAMES:
                raise ScheduleError(
                    f"schedule compute_dtype must be one of "
                    f"{sorted(k for k in _DTYPE_NAMES if k)}, "
                    f"got {d['compute_dtype']!r}")
            d["compute_dtype"] = _DTYPE_NAMES[spec]
        return cls(**d)


def _check_tile(name: str, value: int, *, lane: bool, interpret: bool,
                kernel: str) -> None:
    if value <= 0 or value % SUBLANE:
        raise ScheduleError(
            f"{kernel}: tile {name}={value} must be a positive multiple of "
            f"{SUBLANE} (the f32 sublane count)")
    if lane and not interpret and value % LANE:
        raise ScheduleError(
            f"{kernel}: tile {name}={value} must be a multiple of {LANE} "
            f"(the TPU lane width) for the compiled path; pass "
            f"interpret=True to relax, or pick {name} from "
            f"{{128, 256, 512, ...}}")


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Per-kernel schedule contract: defaults, supported knobs, models.

    ``shape_dims`` names the shape keywords the models take (and, prefixed
    subset ``bucket_dims``, the ones that key the schedule cache — batch
    width ``b`` is deliberately NOT bucketed so one tuned schedule serves
    every matmat width).  Byte models bill points at the rows' own
    ``itemsize`` (4 unless a model takes it; the bf16 compute_dtype cast
    happens in-register, after the VMEM load) and everything else at f32.
    """
    name: str
    default: "Schedule"
    shape_dims: tuple
    bucket_dims: tuple
    reduces: bool                     # output revisited across grid dim 1
    has_bn: bool = True
    has_compute_dtype: bool = False
    has_bd: bool = False              # a feature (d) grid axis
    # models: fn(schedule, **shape) -> bytes / flops
    vmem_model: Optional[Callable[..., int]] = None
    flops_model: Optional[Callable[..., int]] = None
    bytes_model: Optional[Callable[..., int]] = None

    def check(self, s: "Schedule", **shape) -> "Schedule":
        """Validate a fully-resolved schedule for this kernel (+ shape,
        when given, for the VMEM model).  Returns ``s`` for chaining."""
        interp = bool(s.interpret) if s.interpret is not None else False
        if s.bm is None or (self.has_bn and s.bn is None):
            raise ScheduleError(f"{self.name}: schedule tiles not resolved "
                                f"(bm={s.bm}, bn={s.bn})")
        _check_tile("bm", s.bm, lane=False, interpret=interp,
                    kernel=self.name)
        if self.has_bn:
            _check_tile("bn", s.bn, lane=True, interpret=interp,
                        kernel=self.name)
        elif s.bn is not None and s.bn != self.default.bn:
            raise ScheduleError(f"{self.name} has no bn tile (1-D grid); "
                                f"got bn={s.bn}")
        if s.bd is not None:
            if not self.has_bd:
                raise ScheduleError(f"{self.name} has no feature tile bd "
                                    f"(the whole row is one tile); got "
                                    f"bd={s.bd}")
            if s.bd != shape.get("d"):      # a whole row is always legal
                _check_tile("bd", s.bd, lane=True, interpret=interp,
                            kernel=self.name)
        if s.grid_order not in GRID_ORDERS:
            raise ScheduleError(f"{self.name}: grid_order must be one of "
                                f"{GRID_ORDERS}, got {s.grid_order!r}")
        if s.grid_order == "col-major" and self.reduces:
            raise ScheduleError(
                f"{self.name}: grid_order='col-major' is illegal for a "
                f"reducing kernel — the output row tile is accumulated "
                f"across the column grid dimension, which must stay "
                f"innermost")
        if s.acc not in ACCS:
            raise ScheduleError(f"{self.name}: acc must be one of {ACCS}, "
                                f"got {s.acc!r}")
        if s.acc == "scratch" and not self.reduces:
            raise ScheduleError(
                f"{self.name}: acc='scratch' is only meaningful for "
                f"reducing kernels (this kernel writes each output tile "
                f"exactly once)")
        if s.compute_dtype is not None and not self.has_compute_dtype:
            raise ScheduleError(
                f"{self.name} has no compute_dtype knob (its products are "
                f"always f32); got compute_dtype={s.compute_dtype!r}")
        if shape and self.vmem_model is not None:
            need = self.vmem_model(s, **shape)
            if need > VMEM_BYTES:
                raise ScheduleError(
                    f"{self.name}: schedule bm={s.bm} bn={s.bn} bd={s.bd} "
                    f"needs {need} bytes of VMEM per grid cell at shape "
                    f"{shape}, "
                    f"over the {VMEM_BYTES} budget (tiles are "
                    f"double-buffered); shrink the tiles")
        return s


# -- per-kernel VMEM / FLOPs / bytes models ---------------------------------
# Shapes use the kernels' own letters: n/m point counts, d feature dim,
# b block width, k centers, itemsize the bytes of one point coordinate.
# f32 = 4 bytes for everything else (see KernelSpec).

def _rbf_vmem(s, *, n, m, d):
    return (s.bm * d + s.bn * d + s.bm * s.bn) * 4


def _rbf_flops(s, *, n, m, d):
    return n * m * (2 * d + 4)        # |x|^2+|y|^2-2xy + exp per entry


def _rbf_bytes(s, *, n, m, d):
    cells = -(-n // s.bm) * (-(-m // s.bn))
    return cells * (s.bm + s.bn) * d * 4 + n * m * 4


def _fused_d_tile(s, d):
    """The feature tile a fused schedule runs: ``s.bd``, else the whole
    row (the Nystrom twin, and a fit schedule before resolution)."""
    return d if s.bd is None else min(s.bd, d)


def _fused_vmem(s, *, n, m, d, b=8, itemsize=4):
    bd = _fused_d_tile(s, d)
    acc = s.bm * b if s.acc == "scratch" else 0
    tiles_in = (s.bm + s.bn) * bd * itemsize \
        + (s.bn * b + 2 * s.bm + 2 * s.bn) * 4   # V, scale and norm columns
    out = s.bm * b * 4
    gram = s.bm * s.bn * 4 if s.bd is not None else 0   # fit kernel only
    # double-buffered inputs and output, the Gram scratch, the RBF tile
    return 2 * (tiles_in + out) + gram + s.bm * s.bn * 4 + acc * 4


def _fused_flops(s, *, n, m, d, b=8, itemsize=4):
    return n * m * (2 * d + 4 + 2 * b)


def _fused_bytes(s, *, n, m, d, b=8, itemsize=4):
    from repro.kernels.fused_rbf_matmat import pass_bytes
    return pass_bytes(n, m, d, b, bm=s.bm, bn=s.bn, bd=s.bd,
                      itemsize=itemsize)


def _matmat_vmem(s, *, n, m, b=8):
    acc = s.bm * b if s.acc == "scratch" else 0
    return (s.bm * s.bn + s.bn * b + s.bm * b + acc) * 4


def _matmat_flops(s, *, n, m, b=8):
    return 2 * n * m * b


def _matmat_bytes(s, *, n, m, b=8):
    rows = -(-n // s.bm)
    return n * m * 4 + rows * m * b * 4 + n * b * 4


def _assign_vmem(s, *, n, d, k=8):
    return (s.bm * d + k * d + s.bm * k + 2 * s.bm) * 4


def _assign_flops(s, *, n, d, k=8):
    return n * k * (2 * d + 2)


def _assign_bytes(s, *, n, d, k=8):
    rows = -(-n // s.bm)
    return n * d * 4 + rows * k * d * 4 + n * 8


KERNELS: dict[str, KernelSpec] = {}


def _register(spec: KernelSpec) -> KernelSpec:
    KERNELS[spec.name] = spec
    return spec


_register(KernelSpec(
    name="rbf_similarity",
    default=Schedule(bm=128, bn=128),
    shape_dims=("n", "m", "d"), bucket_dims=("n", "m", "d"),
    reduces=False,
    vmem_model=_rbf_vmem, flops_model=_rbf_flops, bytes_model=_rbf_bytes))

_register(KernelSpec(
    name="fused_rbf_matmat",
    default=Schedule(bm=128, bn=128),
    shape_dims=("n", "m", "d", "b", "itemsize"),
    bucket_dims=("n", "m", "d"),
    reduces=True, has_compute_dtype=True, has_bd=True,
    vmem_model=_fused_vmem, flops_model=_fused_flops,
    bytes_model=_fused_bytes))

_register(KernelSpec(
    name="fused_nystrom_matmat",
    default=Schedule(bm=128, bn=128),
    shape_dims=("n", "m", "d", "b"), bucket_dims=("n", "m", "d"),
    reduces=True, has_compute_dtype=True,
    vmem_model=_fused_vmem, flops_model=_fused_flops,
    bytes_model=_fused_bytes))

_register(KernelSpec(
    name="block_matmat",
    default=Schedule(bm=256, bn=512),
    shape_dims=("n", "m", "b"), bucket_dims=("n", "m"),
    reduces=True,
    vmem_model=_matmat_vmem, flops_model=_matmat_flops,
    bytes_model=_matmat_bytes))

_register(KernelSpec(
    name="kmeans_assign",
    default=Schedule(bm=512),
    shape_dims=("n", "d", "k"), bucket_dims=("n", "d"),
    reduces=False, has_bn=False,
    vmem_model=_assign_vmem, flops_model=_assign_flops,
    bytes_model=_assign_bytes))


def spec(kernel: str) -> KernelSpec:
    try:
        return KERNELS[kernel]
    except KeyError:
        raise ScheduleError(
            f"unknown kernel {kernel!r}; schedulable kernels are "
            f"{sorted(KERNELS)}") from None


def as_schedule(value: Any) -> Optional["Schedule"]:
    """Normalize a user-facing schedule value: None / "default" -> None
    (use call-site defaults), a dict -> Schedule, a Schedule passes
    through.  The "auto" string is handled by :func:`resolve` (it needs
    the kernel/shape for the cache lookup)."""
    if value is None or value == "default":
        return None
    if isinstance(value, Schedule):
        return value
    if isinstance(value, dict):
        return Schedule.from_dict(value)
    raise ScheduleError(
        f"schedule must be None, 'default', 'auto', a Schedule or a dict "
        f"of Schedule fields, got {value!r}")


def validate_spec(value: Any) -> Any:
    """Eager constructor-time validation (estimator kwarg): accepts the
    full user-facing domain including "auto"; returns the value."""
    if value == "auto":
        return value
    as_schedule(value)
    return value


def _default_bd(sp: KernelSpec, bd: Optional[int],
                shape: dict) -> Optional[int]:
    if not sp.has_bd:
        return None
    if bd is not None:
        return bd
    if "d" not in shape:
        return None
    from repro.kernels.fused_rbf_matmat import default_d_tile
    return default_d_tile(int(shape["d"]), int(shape.get("itemsize", 4)))


def resolve(kernel: str, schedule: Any = None, *, bm: Optional[int] = None,
            bn: Optional[int] = None, bd: Optional[int] = None,
            compute_dtype: Any = None,
            interpret: Optional[bool] = None,
            **shape) -> tuple["Schedule", str]:
    """Turn a user-facing schedule value + call-site keywords into one
    concrete, legality-checked :class:`Schedule`.

    Returns ``(schedule, source)`` where source is "default" (built from
    the call-site keywords — the pre-schedule behavior, bit-for-bit),
    "explicit" (caller passed a Schedule/dict), "cache" ("auto" hit the
    persistent cache) or "auto-default" ("auto" missed — the default
    schedule runs, and the miss is visible in the cache stats).  A kernel
    with a feature tile gets ``bd`` from the call site, else from the
    default rule for the shape's ``d``.
    """
    sp = spec(kernel)
    if isinstance(compute_dtype, str):
        compute_dtype = _DTYPE_NAMES.get(compute_dtype.lower(),
                                         compute_dtype)
    elif compute_dtype is not None:
        import jax.numpy as jnp
        compute_dtype = jnp.dtype(compute_dtype).name
    fallback = Schedule(
        bm=bm if bm is not None else sp.default.bm,
        bn=(bn if bn is not None else sp.default.bn) if sp.has_bn else None,
        bd=_default_bd(sp, bd, shape),
        compute_dtype=compute_dtype if sp.has_compute_dtype else None,
        interpret=interpret)

    source = "default"
    if schedule == "auto":
        from repro import obs
        from repro.tune.cache import default_cache
        cache = default_cache()
        cached = cache.get(
            kernel, dtype=compute_dtype or "float32",
            **{k: v for k, v in shape.items() if k in sp.bucket_dims})
        if cached is None:
            s, source = fallback, "auto-default"
        else:
            s, source = cached, "cache"
        obs.absorb_stats("tune.cache", cache.stats)
    else:
        s = as_schedule(schedule)
        if s is None:
            s = fallback
        else:
            source = "explicit"
    # fill unset fields from the call site (partial schedules only
    # override what they name)
    s = s.replace(
        bm=s.bm if s.bm is not None else fallback.bm,
        bn=(s.bn if s.bn is not None else fallback.bn) if sp.has_bn
        else s.bn,
        bd=s.bd if s.bd is not None else fallback.bd,
        compute_dtype=s.compute_dtype if s.compute_dtype is not None
        else fallback.compute_dtype,
        interpret=s.interpret if s.interpret is not None else interpret)
    if s.bd is not None and s.bd >= shape.get("d", s.bd + 1):
        s = s.replace(bd=int(shape["d"]))       # one tile: the whole row
    if s.interpret is None:
        from repro.kernels.block_matvec import interpret_default
        s = s.replace(interpret=interpret_default())
    sp.check(s, **{k: v for k, v in shape.items() if k in sp.shape_dims})
    # one count per resolution: a chip run asserts none was interpreted
    from repro import obs
    obs.counter("tune.resolved", kernel=kernel,
                interpret=bool(s.interpret)).inc()
    return s, source
