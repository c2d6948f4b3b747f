"""Executor protocol + registry: the single dispatch point for KAN inference.

Every execution surface (``kan_network_apply(quantized=True)``,
``kan_ffn_apply_quantized``, ``ServeEngine(kan_deploy=True)``,
``launch.serve``) resolves its backend here instead of carrying its own
``backend=`` strings and ``default_interpret()`` probes.  Three registered
backends run the same deployed bundle (duck-typed: ``.dims``, ``.specs``,
``.layers`` (padded {"lut","wc","wb"}, or the int4-packed
{"lut","wcp","wscale","wb"} form for <=4-bit layers),
``.residual_raw``):

  * ``"ref"``    — the layered jnp composition (moved here from
                   ``kan_network_apply_ref``): per-layer SH-LUT dense basis,
                   banded matmul, tanh-rescale + re-quantize boundary.  The
                   bit-exactness oracle for the other two.
  * ``"pallas"`` — the fused multi-layer Pallas pipeline
                   (``kernels.kan_spline.pipeline``), int codes across layer
                   boundaries, one jit per (geometry, bucket).
  * ``"acim"``   — the fused pipeline with the paper's RRAM-ACIM
                   non-idealities injected at the banded-MAC contraction:
                   TM-DV input-generator noise on the entry codes
                   (:func:`repro.core.tmdv.apply_input_noise`), systematic
                   IR-drop attenuation of the conductance rows, and the
                   per-array partial-sum sigma folded into each output tile
                   — all seeded by an explicit PRNG key, so runs reproduce.

Backend selection precedence: explicit argument > :func:`use_backend` scope
> ``REPRO_KAN_BACKEND`` env var > the call site's default.  All backends
share the :mod:`plancache` (batch bucketing + LRU of compiled applies).

Every backend also has a MESH dimension (:mod:`repro.runtime.meshexec`):
when a mesh is bound (explicit ``mesh=`` argument > :func:`use_mesh` scope >
the bundle's ``DeployedKAN.placement``), the cached apply is built as a
``shard_map`` — batch over ``"data"``, each layer's output channels over
``"model"`` per ``dist.sharding.deployed_kan_pspecs``, the boundary
requantizer shard-local and the int boundary codes all-gathered between
layers.  The plan-cache key carries the mesh fingerprint, so sharded and
unsharded entries never collide.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import REGISTRY as _OBS_REGISTRY
from ..obs.trace import profile_scope
from ..core.asp_quant import dense_basis_from_codes, quantize_input
from ..core.cim import CIMConfig
from ..core.tmdv import TMDVConfig, apply_input_noise
from ..kernels import default_interpret
from ..kernels.kan_spline.pipeline import (
    kan_pipeline_impl,
    run_pipeline_layer,
    shard_local_plan,
    unpacked_wc,
    weight_bits,
)
from .meshexec import (
    build_sharded_runner,
    mesh_axis_sizes,
    mesh_fingerprint,
    mesh_from_fingerprint,
    register_mesh,
    resolve_mesh,
    use_mesh,
)
from .plancache import PLAN_CACHE, PlanKey, bucket_batch

__all__ = [
    "ENV_BACKEND_VAR",
    "default_interpret",
    "dispatch_counts",
    "reset_dispatch_counts",
    "register_executor",
    "available_backends",
    "resolve_backend",
    "get_executor",
    "use_backend",
    "use_mesh",
    "resolve_mesh",
    "quiet_cim_config",
    "RefExecutor",
    "PallasExecutor",
    "ACIMExecutor",
]

ENV_BACKEND_VAR = "REPRO_KAN_BACKEND"

# Per-backend dispatch counts (host-side calls through _CachedExecutor):
# always on — one dict increment per KAN execution — so the benchmark legs
# can report them without enabling the obs registry; obs pulls them at
# snapshot time as ``runtime.backend_dispatch{backend=...}``.
DISPATCH_COUNTS: collections.Counter = collections.Counter()


def dispatch_counts() -> dict:
    """Snapshot of per-backend KAN dispatch counts since process start (or
    the last :func:`reset_dispatch_counts`)."""
    return dict(DISPATCH_COUNTS)


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()


# ----------------------------------------------------------------------------
# Registry + resolution
# ----------------------------------------------------------------------------

_EXECUTORS: dict = {}
# innermost use_backend() override; a ContextVar so concurrent engines on
# different threads (or async tasks) cannot clobber each other's scope
_SCOPE_BACKEND: contextvars.ContextVar = contextvars.ContextVar(
    "repro_kan_backend_scope", default=None
)


def register_executor(name: str, executor) -> None:
    _EXECUTORS[name] = executor


def available_backends() -> tuple:
    return tuple(sorted(_EXECUTORS))


def resolve_backend(backend: str | None = None, *,
                    default: str = "pallas") -> str:
    """Resolve a backend name; raises ValueError for unknown names."""
    if backend is None or backend == "auto":
        backend = _SCOPE_BACKEND.get()
    if backend is None:
        backend = os.environ.get(ENV_BACKEND_VAR, "").strip() or None
    if backend is None:
        backend = default
    if backend not in _EXECUTORS:
        raise ValueError(
            f"unknown backend {backend!r}; registered: {available_backends()}"
        )
    return backend


def get_executor(backend: str | None = None, *, default: str = "pallas"):
    return _EXECUTORS[resolve_backend(backend, default=default)]


@contextlib.contextmanager
def use_backend(backend: str | None):
    """Scoped backend override (beats the env var, loses to explicit args).

    ``None`` is a no-op passthrough so callers can plumb an optional choice.
    """
    if backend is not None and backend not in _EXECUTORS:
        raise ValueError(
            f"unknown backend {backend!r}; registered: {available_backends()}"
        )
    token = _SCOPE_BACKEND.set(
        backend if backend is not None else _SCOPE_BACKEND.get()
    )
    try:
        yield
    finally:
        _SCOPE_BACKEND.reset(token)


# ----------------------------------------------------------------------------
# Shared helpers
# ----------------------------------------------------------------------------


def _entry_codes(dep, x, xraw):
    """Entry coding, identical across backends (and to the PR-1 contract):
    KAN stacks quantize x directly; FFN stacks (residual_raw) quantize
    tanh(x) and keep the raw activation for the ReLU branch."""
    spec0 = dep.specs[0]
    if dep.residual_raw:
        xraw = x.astype(jnp.float32) if xraw is None else xraw
        codes = quantize_input(jnp.tanh(xraw), spec0)
    else:
        codes = quantize_input(x, spec0)
        xraw = None
    return codes, xraw


def _logical_layer(lw: dict, lp) -> tuple:
    """Slice one padded deployed layer back to its logical (lut, wc, wb).

    int4-packed layers decode through ``unpacked_wc`` first — the same
    nibble-extract + f32 scale product the kernel computes in-lane, so the
    ref composition stays the bit-exactness oracle for packed layers too.
    """
    nb = lp.spec.num_basis
    wc = unpacked_wc(lw, lp).reshape(lp.fp, nb, lp.op)[: lp.f, :, : lp.o]
    wb = lw["wb"][: lp.f, : lp.o]
    return lw["lut"], wc, wb


def _pad_batch(a, bucket):
    if a is None:
        return None
    return jnp.pad(a, ((0, bucket - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _slice_result(out, b, return_intermediates):
    if return_intermediates:
        y, codes = out
        return y[:b], tuple(c[:b] for c in codes)
    return out[:b]


# Deriving a mesh fingerprint walks every device of the mesh and the plan's
# layer geometry; on the serving hot path that would run per token per
# FFN block just to hit an already-cached entry, so the derivation is
# memoized on (mesh, geometry, bucket).  The registration side effects stay
# per-call (cheap dict writes) so reset_cache()/reset_shard_notes() are
# repopulated by the very next execution.
_MESH_FP_MEMO: dict = {}


def _mesh_key_fingerprint(mesh, dsize, msize, dims, specs, bucket,
                          residual_raw) -> tuple:
    memo_key = (mesh, dims, specs, bucket, residual_raw)
    hit = _MESH_FP_MEMO.get(memo_key)
    if hit is None:
        base = PLAN_CACHE.plan(bucket // dsize, dims, specs,
                               residual_raw=residual_raw)
        _, sharded, notes = shard_local_plan(base, msize)
        hit = (mesh_fingerprint(mesh, sharded), notes)
        if len(_MESH_FP_MEMO) > 256:
            _MESH_FP_MEMO.clear()
        _MESH_FP_MEMO[memo_key] = hit
    fp, notes = hit
    register_mesh(fp, mesh, notes)
    return fp


class _CachedExecutor:
    """Common plancache plumbing: bucket, pad, look up, run, slice.

    Subclasses customize via hooks: ``_flags(**opts)`` (backend statics that
    belong in the cache key), ``_build(plan_key)`` (the per-entry jitted
    apply), ``_run(...)`` (how the apply is invoked), and — for the mesh
    path — ``_mesh_layer_fn`` / ``_mesh_noise_fn`` (the per-shard layer step
    and the per-shard stochastic terms inside the shard_map body).
    """

    name = "?"

    def _flags(self, **opts) -> tuple:
        return ()

    def __call__(self, dep, x, *, xraw=None, interpret=None, key=None,
                 mesh=None, return_intermediates=False, **opts):
        if interpret is None:
            interpret = default_interpret()
        mesh = resolve_mesh(mesh, getattr(dep, "placement", None))
        codes, xraw = _entry_codes(dep, x, xraw)
        b = codes.shape[0]
        if mesh is None:
            bucket = bucket_batch(b)
            mesh_fp = ()
        else:
            dsize, msize = mesh_axis_sizes(mesh)
            # mesh-aware bucketing: the global bucket must split into
            # per-shard slabs of at least one batch tile (>= 8 rows), so the
            # bucket ladder starts at 8 * data_size (divisible by data_size
            # for ANY axis size — data sharding never needs a fallback)
            bucket = bucket_batch(b, lo=8 * dsize)
            mesh_fp = _mesh_key_fingerprint(
                mesh, dsize, msize, tuple(dep.dims), tuple(dep.specs),
                bucket, dep.residual_raw,
            )
        plan_key = PlanKey(
            dims=tuple(dep.dims),
            specs=tuple(dep.specs),
            bucket=bucket,
            residual_raw=dep.residual_raw,
            interpret=interpret,
            backend=self.name,
            flags=self._flags(**opts),
            mesh=mesh_fp,
        )
        _, apply = PLAN_CACHE.get(plan_key, self._build)
        DISPATCH_COUNTS[self.name] += 1
        # annotation-only: on the LM path this fires while the step traces
        with profile_scope(f"kan_spline.{self.name}", record=False):
            out = self._run(apply, _pad_batch(codes, bucket),
                            _pad_batch(xraw, bucket), dep.layers, key,
                            return_intermediates)
        return _slice_result(out, b, return_intermediates)

    def _run(self, apply, codes, xraw, layers, key, return_intermediates):
        return apply(codes, xraw, layers,
                     return_intermediates=return_intermediates)

    def _build(self, key: PlanKey):
        if key.mesh:
            return self._build_sharded(key)
        return self._build_local(key)

    def _build_local(self, key: PlanKey):
        raise NotImplementedError

    # -- the mesh path ---------------------------------------------------

    def _mesh_layer_fn(self, key: PlanKey, local_plan):
        """Per-shard layer step: the fused Pallas kernel on local geometry
        (shared by "pallas" and "acim"; "ref" overrides with its jnp step)."""
        def layer_fn(li, lp, lw, h_codes, h_raw, psum_noise):
            return run_pipeline_layer(
                h_codes, h_raw if lp.residual_raw else None,
                lw, lp, local_plan.bp,
                interpret=key.interpret, psum_noise=psum_noise,
            )
        return layer_fn

    def _mesh_noise_fn(self, key: PlanKey, base_plan, local_plan, sharded):
        return None  # deterministic backends need no per-shard terms

    def _build_sharded(self, key: PlanKey):
        """One shard_mapped apply per (geometry, bucket, mesh fingerprint).

        The per-shard plan divides each sharded layer's padded output dim by
        the model-axis size (whole-column ownership: the MAC never reduces
        across shards) and rebuilds the batch tiling for the per-shard batch
        slab; tuned tile overrides are picked up through the plan cache at
        the per-shard geometry and kept wherever they still divide it.
        """
        mesh = mesh_from_fingerprint(key.mesh)
        dsize, _ = mesh_axis_sizes(mesh)
        base = PLAN_CACHE.plan(key.bucket // dsize, key.dims, key.specs,
                               residual_raw=key.residual_raw)
        local_plan, sharded, _ = shard_local_plan(base, mesh_axis_sizes(mesh)[1])
        assert sharded == key.mesh[3], (sharded, key.mesh)
        runner = build_sharded_runner(
            mesh,
            local_plan=local_plan,
            layer_sharded=sharded,
            residual_raw=key.residual_raw,
            layer_fn=self._mesh_layer_fn(key, local_plan),
            noise_fn=self._mesh_noise_fn(key, base, local_plan, sharded),
        )
        lp0 = base.layers[0]
        logical_o = tuple(lp.o for lp in base.layers)

        @functools.partial(jax.jit, static_argnames=("return_intermediates",))
        def apply(codes, xraw, layers, *extra, return_intermediates=False):
            PLAN_CACHE.record_trace()
            codes = jnp.pad(codes, ((0, 0), (0, lp0.fp - lp0.f)))
            if key.residual_raw:
                xraw = jnp.pad(
                    xraw.astype(jnp.float32), ((0, 0), (0, lp0.fp - lp0.f))
                )
            y, boundary = runner(codes, xraw, layers, *extra)
            y = y[:, : logical_o[-1]]
            if return_intermediates:
                return y, tuple(
                    c[:, : logical_o[li]] for li, c in enumerate(boundary)
                )
            return y

        return base, apply


# ----------------------------------------------------------------------------
# "ref": the layered jnp composition
# ----------------------------------------------------------------------------


def ref_composition(logical_layers, specs, codes, xraw, *,
                    residual_raw: bool, return_intermediates: bool = False):
    """Layered quantized composition over logical (lut, wc, wb) triples.

    Bit-identical to the PR-1 ``kan_layer_apply_quantized`` + tanh-rescale
    chain (same op order, same constants) — the oracle the Pallas pipeline's
    boundary codes are asserted against.
    """
    n = len(logical_layers)
    boundary = []
    y = None
    for li, (lut, wc, wb) in enumerate(logical_layers):
        spec = specs[li]
        basis = dense_basis_from_codes(codes, lut, spec)
        b = codes.shape[0]
        f, nb, o = wc.shape
        y = basis.reshape(b, f * nb) @ wc.reshape(f * nb, o)
        if residual_raw:
            resid = jax.nn.relu(xraw)
        else:
            resid = jax.nn.relu(
                spec.lo + codes.astype(jnp.float32) * spec.code_step
            )
        y = y + resid @ wb
        if li < n - 1:
            nxt = specs[li + 1]
            if residual_raw:
                xraw = y
                codes = quantize_input(jnp.tanh(y), nxt)
            else:
                h = jnp.tanh(y) * (0.5 * (nxt.hi - nxt.lo)) \
                    + 0.5 * (nxt.hi + nxt.lo)
                codes = quantize_input(h, nxt)
            boundary.append(codes)
    if return_intermediates:
        return y, tuple(boundary)
    return y


def _ref_padded_layer(lp, lw, codes, xraw, psum_noise=None):
    """One layer of the ref composition on PADDED per-shard geometry.

    The mesh path's jnp analogue of ``run_pipeline_layer``: same op order as
    the kernel (dense SH-LUT basis -> banded MAC -> fused ReLU branch ->
    kernel-style boundary re-code), operating on the padded weights a shard
    actually holds (zero-padded lanes contribute nothing).
    """
    spec = lp.spec
    b = codes.shape[0]
    basis = dense_basis_from_codes(codes, lw["lut"], spec)
    y = jax.lax.dot_general(
        basis.reshape(b, lp.fp * spec.num_basis),
        unpacked_wc(lw, lp),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if lp.residual_raw:
        resid = xraw.astype(jnp.float32)
    else:
        resid = spec.lo + codes.astype(jnp.float32) * spec.code_step
    y = y + jnp.maximum(resid, 0.0) @ lw["wb"].astype(jnp.float32)
    if psum_noise is not None:
        y = y + psum_noise
    if not lp.emit_codes:
        return y, None
    nxt = lp.next_spec
    h = jnp.tanh(y) * (0.5 * (nxt.hi - nxt.lo)) + 0.5 * (nxt.hi + nxt.lo)
    q = jnp.floor((h - nxt.lo) / nxt.code_step + 0.5).astype(jnp.int32)
    return y, jnp.clip(q, 0, nxt.num_codes - 1)


class RefExecutor(_CachedExecutor):
    name = "ref"

    def _mesh_layer_fn(self, key: PlanKey, local_plan):
        def layer_fn(li, lp, lw, h_codes, h_raw, psum_noise):
            return _ref_padded_layer(
                lp, lw, h_codes, h_raw if lp.residual_raw else None,
                psum_noise=psum_noise,
            )
        return layer_fn

    def _build_local(self, key: PlanKey):
        plan = PLAN_CACHE.plan(key.bucket, key.dims, key.specs,
                               residual_raw=key.residual_raw)

        @functools.partial(jax.jit, static_argnames=("return_intermediates",))
        def apply(codes, xraw, layers, return_intermediates=False):
            PLAN_CACHE.record_trace()
            logical = [_logical_layer(lw, lp)
                       for lw, lp in zip(layers, plan.layers)]
            return ref_composition(
                logical, key.specs, codes, xraw,
                residual_raw=key.residual_raw,
                return_intermediates=return_intermediates,
            )

        return plan, apply


# ----------------------------------------------------------------------------
# "pallas": the fused pipeline
# ----------------------------------------------------------------------------


class PallasExecutor(_CachedExecutor):
    name = "pallas"

    def _build_local(self, key: PlanKey):
        plan = PLAN_CACHE.plan(key.bucket, key.dims, key.specs,
                               residual_raw=key.residual_raw)

        @functools.partial(jax.jit, static_argnames=("return_intermediates",))
        def apply(codes, xraw, layers, return_intermediates=False):
            PLAN_CACHE.record_trace()
            return kan_pipeline_impl(
                codes, xraw, layers, plan,
                interpret=key.interpret,
                return_intermediates=return_intermediates,
            )

        return plan, apply


# ----------------------------------------------------------------------------
# "acim": the fused pipeline + RRAM-ACIM non-idealities
# ----------------------------------------------------------------------------


def quiet_cim_config() -> CIMConfig:
    """A CIMConfig with every non-ideality zeroed (bit-exact vs "pallas")."""
    return CIMConfig(
        ir_gamma=0.0,
        sigma_ps_ref=0.0,
        input_gen=TMDVConfig(sigma_v_ref=0.0, sigma_t=0.0),
    )


def _irdrop_row_gain(lp, cfg: CIMConfig, perm=None) -> np.ndarray | None:
    """Static per-row conductance gain (Fp*NB, 1), or None when IR-drop is off.

    Mirrors ``core.cim.cim_matmul``'s systematic term at typical column load
    (col_load == 1): physical row p of each array attenuates by
    ``ir_scale * (p+1)/rows``; deployment calibration divides out the
    mean-distance attenuation, leaving the placement-dependent residual.
    By default logical rows map to physical positions in natural banded
    order (feature-major, as the weights are flattened); ``perm`` — a
    KAN-SAM placement with ``perm[p] = logical row at physical position p``
    (see ``core.sam.sam_permutation``) — relocates each logical row's
    IR-drop exposure to its SAM slot instead.  Zero-padded rows past the
    logical row count keep gain 1 (they hold no conductance).
    """
    ir = cfg.ir_scale()
    if ir == 0.0:
        return None
    rows = cfg.array_rows
    nb = lp.spec.num_basis
    n_logical = lp.f * nb
    r = np.arange(lp.fp * nb)
    if perm is None:
        pos = r
    else:
        perm = np.asarray(perm)
        if perm.shape != (n_logical,):
            raise ValueError(
                f"sam perm has {perm.shape} entries; layer has {n_logical} "
                "logical rows"
            )
        inv = np.empty(n_logical, np.int64)
        inv[perm] = np.arange(n_logical)
        pos = np.where(r < n_logical, inv[np.minimum(r, n_logical - 1)], r)
    dist = ((pos % rows) + 1.0) / rows
    factor = 1.0 - ir * dist
    comp = 1.0 - ir * (rows + 1.0) / (2.0 * rows)
    gain = np.where(r < n_logical, factor / comp, 1.0)
    return gain.astype(np.float32)[:, None]


def _n_arrays(lp, cfg: CIMConfig) -> int:
    """Physical macro count one output column's MAC spans."""
    return max(1, -(-(lp.f * lp.spec.num_basis) // cfg.array_rows))


@dataclasses.dataclass
class ACIMExecutor(_CachedExecutor):
    """Fused pipeline with measured non-idealities at the MAC contraction.

    The injection points (all gated so a zeroed config traces the exact same
    program as "pallas"):

      * entry codes -> :func:`apply_input_noise` (TM-DV voltage/time sigma),
        re-rounded to the nearest valid ASP code;
      * conductance rows -> systematic IR-drop gain (mean-compensated, as on
        the calibrated 22nm prototype); an optional per-layer KAN-SAM
        placement (``sam_perms=``, see ``core.sam``) relocates each row's
        IR-drop exposure to its mapped physical slot, so the co-design
        search can score SAM on/off on the same fused backend;
      * each (batch, out) tile -> additive Gaussian partial-sum error with
        per-channel std ``sigma_ps * sqrt(n_arrays) * x_max * lut_lsb *
        w_lsb[o]`` — the float-domain image of ``cim_matmul``'s code-domain
        sigma, accumulated over the arrays a column spans.  Injected on the
        first contraction step, so the fused boundary requantizer propagates
        the error to the next layer's int codes.

    ``key`` seeds every stochastic term; the same key reproduces the run.
    When no key is supplied (e.g. the serving path, where ``ffn`` has no key
    plumbing), a default key is folded with a digest of the entry codes, so
    distinct layers/steps/tokens draw decorrelated noise while staying fully
    deterministic for identical inputs.
    """

    cim: CIMConfig = dataclasses.field(
        default_factory=lambda: CIMConfig(ir_gamma=0.06, sigma_ps_ref=0.05)
    )
    name: str = dataclasses.field(default="acim", init=False)

    def _flags(self, cim: CIMConfig | None = None, sam_perms=None,
               **_opts) -> tuple:
        flags = ("cim", self.cim if cim is None else cim)
        if sam_perms is not None:
            # per-layer KAN-SAM placements (or None to keep natural order);
            # tuples so the cache key stays hashable
            flags += ("sam", tuple(
                None if p is None else tuple(int(i) for i in np.asarray(p))
                for p in sam_perms
            ))
        return flags

    def _run(self, apply, codes, xraw, layers, key, return_intermediates):
        if key is None:
            key = jax.random.fold_in(
                jax.random.PRNGKey(0), jnp.sum(codes, dtype=jnp.uint32)
            )
        return apply(codes, xraw, layers, key,
                     return_intermediates=return_intermediates)

    def _statics(self, key: PlanKey) -> tuple:
        """(cfg, sam_perms, has_input_noise, has_psum) from the key."""
        cfg = key.flags[1]
        sam_perms = None
        if len(key.flags) >= 4 and key.flags[2] == "sam":
            sam_perms = key.flags[3]
        tm = cfg.input_gen
        has_input_noise = (not cfg.deterministic) and (
            tm.sigma_v > 0.0 or tm.sigma_t > 0.0
        )
        has_psum = (not cfg.deterministic) and cfg.sigma_ps_ref > 0.0
        return cfg, sam_perms, has_input_noise, has_psum

    @staticmethod
    def _layer_psum_std(cfg, lp, lw):
        """Per-channel partial-sum sigma of one layer, at ITS bit widths.

        ``x_max`` is the layer's LUT code ceiling (2**lut_bits - 1) and the
        per-channel weight LSB divides by the layer's signed weight-code
        ceiling (2**(w_bits-1) - 1, so a 4-bit layer's max |code| is 7 —
        its LSB, and hence its partial-sum error, is correspondingly
        coarser).  Packed layers decode through ``unpacked_wc`` first.
        """
        x_max = float(2 ** lp.spec.lut_bits - 1)
        w_qmax = float(2 ** (weight_bits(lp.spec) - 1) - 1)
        w_lsb = jnp.max(jnp.abs(unpacked_wc(lw, lp)), axis=0) / w_qmax
        lut_lsb = jnp.max(lw["lut"]) / x_max
        return (cfg.sigma_ps() * np.sqrt(_n_arrays(lp, cfg))
                * x_max * lut_lsb) * w_lsb

    def _row_gains(self, key: PlanKey, plan) -> tuple:
        cfg, sam_perms, *_ = self._statics(key)
        return tuple(
            _irdrop_row_gain(
                lp, cfg, perm=sam_perms[li] if sam_perms is not None else None
            )
            for li, lp in enumerate(plan.layers)
        )

    def _mesh_layer_fn(self, key: PlanKey, local_plan):
        """The pallas step with the systematic IR-drop gains folded into the
        shard-local conductance columns.  The gains are a full-length ROW
        vector (the contraction axis stays whole on every shard), so they
        broadcast unchanged against the shard's column slab."""
        base_fn = super()._mesh_layer_fn(key, local_plan)
        row_gains = self._row_gains(key, local_plan)

        def layer_fn(li, lp, lw, h_codes, h_raw, psum_noise):
            if row_gains[li] is not None:
                # gains break the uniform per-channel scale: decode packed
                # layers to the f32 operand before applying them (mirrors
                # the local path)
                wc = unpacked_wc(lw, lp) * jnp.asarray(row_gains[li])
                lw = {"lut": lw["lut"], "wc": wc, "wb": lw["wb"]}
            return base_fn(li, lp, lw, h_codes, h_raw, psum_noise)

        return layer_fn

    def _mesh_noise_fn(self, key: PlanKey, base_plan, local_plan, sharded):
        """Per-shard stochastic terms inside the shard_map body.

        The PRNG key splits per shard: the data index is folded in first
        (every batch slab draws decorrelated noise), and the model index is
        folded into a layer's partial-sum draw ONLY when that layer's
        columns are sharded — replicated layers must see identical noise on
        every model replica, and entry-code noise (codes are replicated
        across "model") likewise folds the data index only.  Per-tile sigma
        stays consistent under sharding by construction: each shard owns
        whole MAC columns, so the per-shard ``n_arrays`` (the physical
        macros one column's contraction spans) equals the unsharded value,
        and the per-channel ``w_lsb`` computed from the local column slab
        matches the same columns of the global weight matrix.
        """
        cfg, _, has_input_noise, has_psum = self._statics(key)
        if not (has_input_noise or has_psum):
            return None
        spec0 = key.specs[0]
        tm = cfg.input_gen

        def noise_fn(codes, layers, noise_key, ctx):
            k = jax.random.fold_in(noise_key, ctx.data_index)
            if has_input_noise:
                k, k_in = jax.random.split(k)
                eff = apply_input_noise(codes, tm, k_in)
                codes = jnp.clip(
                    jnp.floor(eff + 0.5).astype(jnp.int32),
                    0, spec0.num_codes - 1,
                )
            if not has_psum:
                return codes, None
            noises = []
            for li, (lp, lw) in enumerate(zip(local_plan.layers, layers)):
                std = self._layer_psum_std(cfg, lp, lw)
                k, k_ps = jax.random.split(k)
                if ctx.layer_sharded[li]:
                    k_ps = jax.random.fold_in(k_ps, ctx.model_index)
                noises.append(std[None, :] * jax.random.normal(
                    k_ps, (local_plan.bp, lp.op), jnp.float32))
            return codes, tuple(noises)

        return noise_fn

    def _build_local(self, key: PlanKey):
        cfg, sam_perms, has_input_noise, has_psum = self._statics(key)
        plan = PLAN_CACHE.plan(key.bucket, key.dims, key.specs,
                               residual_raw=key.residual_raw)
        spec0 = key.specs[0]
        tm = cfg.input_gen
        row_gains = self._row_gains(key, plan)

        @functools.partial(jax.jit, static_argnames=("return_intermediates",))
        def apply(codes, xraw, layers, noise_key, return_intermediates=False):
            PLAN_CACHE.record_trace()
            if has_input_noise:
                noise_key, k_in = jax.random.split(noise_key)
                eff = apply_input_noise(codes, tm, k_in)
                codes = jnp.clip(
                    jnp.floor(eff + 0.5).astype(jnp.int32),
                    0, spec0.num_codes - 1,
                )
            acim_layers = []
            noises = [] if has_psum else None
            for li, (lp, lw) in enumerate(zip(plan.layers, layers)):
                if has_psum:
                    # per-channel weight LSB recovered from the int-code
                    # storage at the layer's own bit widths; padded output
                    # channels have zero weights -> zero sigma, keeping the
                    # padded lanes noiseless.
                    std = self._layer_psum_std(cfg, lp, lw)
                    noise_key, k_ps = jax.random.split(noise_key)
                    noises.append(std[None, :] * jax.random.normal(
                        k_ps, (plan.bp, lp.op), jnp.float32))
                if row_gains[li] is not None:
                    # the per-row conductance gains break the uniform
                    # per-channel scale, so a packed layer falls back to
                    # the unpacked f32 operand for this noisy program
                    # (quiet configs never reach here: same keys, same
                    # packed kernel as "pallas")
                    wc = unpacked_wc(lw, lp) * jnp.asarray(row_gains[li])
                    acim_layers.append(
                        {"lut": lw["lut"], "wc": wc, "wb": lw["wb"]})
                else:
                    acim_layers.append(lw)
            return kan_pipeline_impl(
                codes, xraw, tuple(acim_layers), plan,
                interpret=key.interpret,
                psum_noises=tuple(noises) if noises is not None else None,
                return_intermediates=return_intermediates,
            )

        return plan, apply


register_executor("ref", RefExecutor())
register_executor("pallas", PallasExecutor())
register_executor("acim", ACIMExecutor())


def _obs_collect() -> dict:
    """Per-backend dispatch counts under the documented labeled series."""
    return {
        ("runtime.backend_dispatch", (("backend", name),)): count
        for name, count in sorted(DISPATCH_COUNTS.items())
    }


_OBS_REGISTRY.register_collector(_obs_collect)
