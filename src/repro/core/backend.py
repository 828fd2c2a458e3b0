"""Unified kernel-backend dispatch: one compute registry for every estimator.

The paper's thesis (§12) is that the overlapping-block weak-memory scheme is
*system-agnostic* — the identical map-reduce runs on Spark executors or on
GPU shared-memory tiles.  This module makes the execution substrate a
pluggable policy instead of a fork in every call site: every weak-memory
estimator in the repo reduces to a handful of primitive contractions, and a
:class:`Backend` supplies one implementation of each:

  ``lagged_sums(x, max_lag)``            S(h) = Σ_k x_k x_{k+h}ᵀ (ragged
                                         full sums, the autocovariance core)
  ``masked_lagged_sums(y, mask, H)``     Σ_{s: mask[s]} y_s y_{s+h}ᵀ — the
                                         streaming ChunkKernel form
  ``windowed_moments(x, window)``        per-window [Σx, Σx²] (rolling
                                         mean/variance)
  ``segment_fft_power(segs, taper)``     per-segment |rfft|² (Welch / Whittle)
  ``banded_matvec(diags, x)``            x̂ = A x for b-banded A (§6.1)
  ``fused_lagged_moments(y, mask, H, w)``  masked lagged sums AND masked
                                         windowed-moment sums from ONE
                                         traversal — the fused-plan
                                         primitive (`repro.core.plan`): on
                                         the Pallas backend both statistics
                                         are emitted from a single VMEM
                                         staging of each tile (one HBM read
                                         instead of two).  ``w`` may be an
                                         int (→ (2, d) moments) or a tuple
                                         of DISTINCT windows (→ (K, 2, d)):
                                         every window is accumulated from
                                         the same resident tile, so a plan
                                         tracking rolling moments at K
                                         horizons still costs one traversal
  ``segment_csd(segs, taper)``           per-segment complex cross-spectral
                                         products rfft_i·conj(rfft_j) — the
                                         Whittle/coherence core; on Pallas
                                         four real contractions of the
                                         resident segment, recombined to
                                         complex64 outside the kernel
  ``fused_plan_update(y, mask, z0, …)``  the fused-plan MEGAKERNEL: masked
                                         lagged sums + K moment windows +
                                         M Welch segment-power accumulators
                                         from ONE grid walk — on Pallas
                                         each chunk tile is staged into
                                         VMEM once and feeds every member
                                         family (one launch, one HBM read,
                                         down from one per family); on jnp
                                         a composition of the primitives
                                         above (the parity oracle)

Backends in the registry:

  ``"jnp"``     pure jax.numpy on whatever XLA device is active — the
                correctness oracle and the CPU/cluster default.
  ``"pallas"``  explicit VMEM tile kernels (`repro.kernels.window_stats`,
                `repro.kernels.banded_matvec`,
                `repro.kernels.segment_dft`) — the TPU re-instantiation of
                the paper's §12 GPU shared-memory scheme.  Compiled for the
                TPU; off the TPU it exists only when a caller registers an
                interpret-mode instance (``PallasBackend(interpret=True)``,
                as the test suite does), so no run lands in interpret mode
                unasked.  Every primitive has a real kernel: the spectral ones
                evaluate the fixed-L real DFT as tiled matmuls against
                precomputed twiddle/window matrices, and
                ``fused_plan_update`` is a persistent MEGAKERNEL serving a
                whole fused plan from one grid walk.  Tile sizes resolve
                through the calibrated block table
                (``calibrate(tune_blocks=True)``) unless pinned explicitly.
  ``"auto"``    per-call policy (the default): each primitive routes to
                Pallas once its problem size crosses a per-primitive
                threshold (`repro.core.calibrate`).  The thresholds resolve
                lazily at first dispatch, without measuring anything: a
                table the user installed (``python -m repro.core.calibrate
                --tune`` / ``--bless``), else the built-in default table
                (off-accelerator that table says "always jnp": interpret
                mode is a testing vehicle, not a serving path).

Registering a new backend (a GPU Triton port, a CPU-vectorized build, …):

    class TritonBackend: ...    # implement the primitive contractions
    register_backend("triton", TritonBackend())
    gamma = autocovariance(x, 8, backend="triton")

Every estimator (`estimators.stats`, `estimators.spectral`,
`estimators.yule_walker`, `estimators.arma`, `estimators.spatial`), the
streaming engine (`core.streaming` — its ChunkKernels are built from
``masked_lagged_sums`` / ``segment_fft_power``), the block/sharded paths
(`core.mapreduce`, `parallel.sharding`), and the serving ingest lanes
(`serving.rolling`) accept ``backend=`` (a name or a Backend instance) and
route through this registry — changing where the math runs is a config knob,
never an estimator rewrite.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Protocol, Union, runtime_checkable

import jax
import jax.numpy as jnp

__all__ = [
    "Backend",
    "JnpBackend",
    "PallasBackend",
    "AutoBackend",
    "CircuitBreakerBackend",
    "PRIMITIVE_NAMES",
    "register_backend",
    "get_backend",
    "list_backends",
    "set_default_backend",
]

BackendSpec = Union[None, str, "Backend"]

# The canonical primitive-contraction names of the Backend protocol below —
# what the circuit breaker quarantines per-name and what
# `repro.core.calibrate` measures per-name.
PRIMITIVE_NAMES: tuple = (
    "lagged_sums",
    "masked_lagged_sums",
    "windowed_moments",
    "segment_fft_power",
    "segment_csd",
    "banded_matvec",
    "fused_lagged_moments",
    "fused_plan_update",
)


@runtime_checkable
class Backend(Protocol):
    """The primitive contractions every weak-memory estimator reduces to."""

    name: str

    def lagged_sums(self, x: jax.Array, max_lag: int) -> jax.Array:
        """(n, d) → (max_lag+1, d, d): S(h) = Σ_{k=0}^{n-1-h} x_k x_{k+h}ᵀ."""
        ...

    def masked_lagged_sums(
        self, y_padded: jax.Array, start_mask: jax.Array, max_lag: int
    ) -> jax.Array:
        """Σ_{s: start_mask[s]} y_s y_{s+h}ᵀ → (max_lag+1, d, d).

        ``y_padded`` carries ≥ L rows (L = len(start_mask)); rows
        [s, s+max_lag] are read for every unmasked start (zero-extended when
        shorter than L + max_lag).  This is the streaming ChunkKernel form.
        """
        ...

    def windowed_moments(self, x: jax.Array, window: int) -> jax.Array:
        """(n, d) → (n-window+1, 2, d) of per-window [Σ x, Σ x²]."""
        ...

    def segment_fft_power(
        self, segments: jax.Array, taper: jax.Array, detrend: bool = True
    ) -> jax.Array:
        """(S, W, d) segments → (S, W//2+1, d) per-segment |rfft|² power."""
        ...

    def banded_matvec(self, diags: jax.Array, x: jax.Array) -> jax.Array:
        """(d, 2b+1) stacked diagonals, x (..., d) → A x (..., d)."""
        ...

    def fused_lagged_moments(
        self,
        y_padded: jax.Array,
        start_mask: jax.Array,
        max_lag: int,
        window: "int | tuple",
    ) -> tuple:
        """One traversal → (lag (max_lag+1, d, d), mom).

        ``lag`` is exactly ``masked_lagged_sums(y_padded, start_mask,
        max_lag)``; ``mom`` is Σ_{s: mask} Σ_{j<w} [y_{s+j}, y²_{s+j}]
        — the product-monoid stat a fused statistics plan carries.
        ``window`` is an int (``mom`` is (2, d)) or a tuple of distinct
        windows (``mom`` is (len(window), 2, d), row k for ``window[k]``);
        either way the series is walked once.
        """
        ...

    def segment_csd(
        self, segments: jax.Array, taper: jax.Array, detrend: bool = True
    ) -> jax.Array:
        """(S, W, d) segments → (S, W//2+1, d, d) complex64 per-segment
        cross-spectral products rfft_i · conj(rfft_j) (Hermitian in i, j)."""
        ...

    def fused_plan_update(
        self,
        y_padded: jax.Array,
        start_mask: jax.Array,
        z0: jax.Array,
        max_lag: int,
        windows: tuple = (),
        seg_lens: tuple = (),
        seg_steps: tuple = (),
        tapers: tuple = (),
        detrend: bool = True,
        stage_dtype: "str | None" = None,
    ) -> tuple:
        """EVERY fused-plan member family from one traversal of the chunk.

        Returns ``(lag, mom, psds, n_segs)``: ``lag`` is
        ``masked_lagged_sums(y_padded, start_mask, max_lag)``; ``mom`` is
        the (K, 2, d) multi-window moment stat of ``fused_lagged_moments``
        (None when ``windows`` is empty); ``psds[j]`` is the (W_j//2+1, d)
        sum of detrended, tapered |rfft|² over every Welch segment of
        member j — segments start at local rows ``c`` with ``(z0 + c) %
        seg_steps[j] == 0``, ``c < L`` and ``start_mask[c]`` — and
        ``n_segs[j]`` counts them.  ``stage_dtype`` (e.g. "bfloat16")
        narrows the series staging; accumulation stays f32.
        """
        ...


# f32 contractions: TPU's default matmul precision rounds f32 operands to
# bf16, which the statistics cannot afford.
_F32 = jax.lax.Precision.HIGHEST


def _as_2d(x: jax.Array) -> jax.Array:
    return x[:, None] if x.ndim == 1 else x


class JnpBackend:
    """Pure jax.numpy implementations — the correctness oracle.

    All accumulation happens in float32 whatever the input dtype, matching
    the Pallas kernels' ``preferred_element_type`` so cross-backend parity
    holds for bf16 inputs too.  Contractions ask for f32 precision: on TPU
    the default would round f32 operands to bf16.
    """

    name = "jnp"

    def lagged_sums(self, x: jax.Array, max_lag: int) -> jax.Array:
        x = _as_2d(x).astype(jnp.float32)
        n = x.shape[0]

        if n <= max_lag:
            # Tiny series (every lag ragged): direct masked form, O(n·H·d²).
            def one_ragged(h):
                idx = jnp.arange(n)
                valid = (idx + h) <= (n - 1)
                shifted = x[jnp.clip(idx + h, 0, n - 1)]
                shifted = jnp.where(valid[:, None], shifted, 0.0)
                return jnp.einsum("ti,tj->ij", x, shifted, precision=_F32)

            return jax.vmap(one_ragged)(jnp.arange(max_lag + 1))

        def one(h):
            head = jax.lax.dynamic_slice_in_dim(x, 0, n - max_lag, axis=0)
            shifted = jax.lax.dynamic_slice_in_dim(x, h, n - max_lag, axis=0)
            # Only the common full-length prefix enters this vectorized form;
            # the ragged tail (k in [n-max_lag, n-h)) is added below.
            return jnp.einsum("ti,tj->ij", head, shifted, precision=_F32)

        full = jax.vmap(one)(jnp.arange(max_lag + 1))

        # Ragged tail: for lag h, centers k = n-max_lag .. n-1-h.
        def tail(h):
            ks = jnp.arange(max_lag)  # offsets into the tail region
            k = n - max_lag + ks
            valid = (k + h) <= (n - 1)
            xk = x[jnp.clip(k, 0, n - 1)]
            xkh = x[jnp.clip(k + h, 0, n - 1)]
            contrib = jnp.einsum("ti,tj->tij", xk, xkh, precision=_F32)
            return jnp.sum(jnp.where(valid[:, None, None], contrib, 0.0), axis=0)

        if max_lag > 0:
            full = full + jax.vmap(tail)(jnp.arange(max_lag + 1))
        return full

    def masked_lagged_sums(
        self, y_padded: jax.Array, start_mask: jax.Array, max_lag: int
    ) -> jax.Array:
        y_padded = _as_2d(y_padded).astype(jnp.float32)
        L = start_mask.shape[0]
        need = L + max_lag
        if y_padded.shape[0] < need:
            y_padded = jnp.pad(y_padded, ((0, need - y_padded.shape[0]), (0, 0)))
        head = jnp.where(start_mask[:, None], y_padded[:L], 0.0)

        def one(h):
            shifted = jax.lax.dynamic_slice_in_dim(y_padded, h, L, axis=0)
            return jnp.einsum("ti,tj->ij", head, shifted, precision=_F32)

        return jax.vmap(one)(jnp.arange(max_lag + 1))

    def windowed_moments(self, x: jax.Array, window: int) -> jax.Array:
        x = _as_2d(x).astype(jnp.float32)
        n, d = x.shape
        if n - window + 1 < 1:
            raise ValueError(f"series of length {n} has no full window of width {window}")
        zero = jnp.zeros((1, d), jnp.float32)
        cs = jnp.concatenate([zero, jnp.cumsum(x, axis=0)])
        cs2 = jnp.concatenate([zero, jnp.cumsum(x * x, axis=0)])
        s1 = cs[window:] - cs[:-window]
        s2 = cs2[window:] - cs2[:-window]
        return jnp.stack([s1, s2], axis=1)

    def segment_fft_power(
        self, segments: jax.Array, taper: jax.Array, detrend: bool = True
    ) -> jax.Array:
        segments = segments.astype(jnp.float32)

        def one(seg):
            if detrend:
                seg = seg - seg.mean(axis=0)
            f = jnp.fft.rfft(seg * taper[:, None], axis=0)
            return jnp.abs(f) ** 2

        return jax.vmap(one)(segments)

    def banded_matvec(self, diags: jax.Array, x: jax.Array) -> jax.Array:
        d, w = diags.shape
        b = (w - 1) // 2
        # gather the b-halo neighbourhood of every row: (..., d, 2b+1)
        cols = jnp.arange(d)[:, None] + jnp.arange(-b, b + 1)[None, :]
        valid = (cols >= 0) & (cols < d)
        xn = jnp.take(x.astype(jnp.float32), jnp.clip(cols, 0, d - 1), axis=-1)
        xn = jnp.where(valid, xn, 0.0)
        return jnp.einsum(
            "...dw,dw->...d", xn, diags.astype(jnp.float32), precision=_F32
        )

    def fused_lagged_moments(
        self,
        y_padded: jax.Array,
        start_mask: jax.Array,
        max_lag: int,
        window: "int | tuple",
    ) -> tuple:
        # leaf-module import (jnp-only): the window validation is shared
        # with the Pallas wrappers without a kernels → core back-edge
        from ..kernels.window_stats.ref import normalize_windows

        windows, single = normalize_windows(window)
        y_padded = _as_2d(y_padded).astype(jnp.float32)
        L = start_mask.shape[0]
        w_max = max(windows)
        need = L + max(max_lag, w_max - 1)
        if y_padded.shape[0] < need:
            y_padded = jnp.pad(y_padded, ((0, need - y_padded.shape[0]), (0, 0)))
        lag = self.masked_lagged_sums(y_padded, start_mask, max_lag)

        # windowed sums per start via ONE cumsum pass shared by every window
        # — no second traversal of the series, and K windows cost K slices.
        zero = jnp.zeros((1, y_padded.shape[1]), jnp.float32)
        y = y_padded[: L + w_max - 1]
        cs = jnp.concatenate([zero, jnp.cumsum(y, axis=0)])
        cs2 = jnp.concatenate([zero, jnp.cumsum(y * y, axis=0)])
        m = start_mask.astype(jnp.float32)[:, None]

        moms = []
        for w in windows:
            s1 = cs[w : L + w] - cs[:L]
            s2 = cs2[w : L + w] - cs2[:L]
            moms.append(
                jnp.stack([jnp.sum(m * s1, axis=0), jnp.sum(m * s2, axis=0)])
            )
        mom = jnp.stack(moms)
        return lag, (mom[0] if single else mom)

    def segment_csd(
        self, segments: jax.Array, taper: jax.Array, detrend: bool = True
    ) -> jax.Array:
        segments = segments.astype(jnp.float32)
        taper = taper.astype(jnp.float32)

        def one(seg):
            if detrend:
                seg = seg - seg.mean(axis=0)
            f = jnp.fft.rfft(seg * taper[:, None], axis=0)  # (F, d)
            return jnp.einsum("fi,fj->fij", f, jnp.conj(f), precision=_F32)

        return jax.vmap(one)(segments)

    def fused_plan_update(
        self,
        y_padded: jax.Array,
        start_mask: jax.Array,
        z0: jax.Array,
        max_lag: int,
        windows: tuple = (),
        seg_lens: tuple = (),
        seg_steps: tuple = (),
        tapers: tuple = (),
        detrend: bool = True,
        stage_dtype: "str | None" = None,
    ) -> tuple:
        """Composition oracle: the megakernel's contract restated as calls
        to the existing primitives (lag/moments via ``fused_lagged_moments``,
        spectra via the Welch candidate gather + ``segment_fft_power``).
        ``stage_dtype`` rounds the series through the staging dtype first,
        mirroring the Pallas kernel's narrowed HBM↔VMEM stream bit-for-bit.
        """
        windows = tuple(windows)
        y_padded = _as_2d(y_padded)
        if stage_dtype is not None:
            y_padded = y_padded.astype(jnp.dtype(stage_dtype))
        y_padded = y_padded.astype(jnp.float32)
        L = start_mask.shape[0]
        w_max = max(windows) if windows else 1
        l_max = max(seg_lens) if seg_lens else 1
        need = L + max(max_lag, w_max - 1, l_max - 1)
        if y_padded.shape[0] < need:
            y_padded = jnp.pad(y_padded, ((0, need - y_padded.shape[0]), (0, 0)))

        if windows:
            lag, mom = self.fused_lagged_moments(
                y_padded, start_mask, max_lag, windows
            )
        else:
            lag = self.masked_lagged_sums(y_padded, start_mask, max_lag)
            mom = None

        z0 = jnp.asarray(z0, jnp.int32)
        psds, n_segs = [], []
        for Lseg, step, taper in zip(seg_lens, seg_steps, tapers):
            K = L // step + 1  # static bound on aligned starts in [z0, z0+L)
            base = (-z0) % step
            cand = base + jnp.arange(K) * step
            valid = (cand < L) & start_mask[jnp.clip(cand, 0, L - 1)]
            wins = jax.vmap(
                lambda s: jax.lax.dynamic_slice_in_dim(y_padded, s, Lseg, axis=0)
            )(jnp.clip(cand, 0, L - 1))
            power = self.segment_fft_power(wins, taper, detrend)
            psds.append(
                jnp.sum(jnp.where(valid[:, None, None], power, 0.0), axis=0)
            )
            n_segs.append(jnp.sum(valid.astype(jnp.float32)))
        return lag, mom, tuple(psds), tuple(n_segs)


class PallasBackend:
    """Explicit VMEM tile kernels (the paper's §12 scheme on TPU).

    Args:
      block_t: core tile length for the windowed-contraction kernels and
        the fused-plan megakernel.
      block_rows: row tile for the banded matvec.
      block_s: segments staged per grid step in the segment-DFT kernels.
      interpret: run the kernels in Pallas interpret mode.  ``None``
        (default) means compiled, and raises off the TPU — interpret mode
        is never chosen for the caller; pass ``True`` to validate the
        kernels on CPU.

    Every block argument defaults to ``None`` — the ops entry points then
    resolve the tile size through the calibrated per-platform block table
    (`repro.kernels.tiling.resolve_block`; written by
    ``calibrate(tune_blocks=True)``), falling back to the built-in
    defaults.  Pass an int to pin a size explicitly (tests, the tuner).
    """

    name = "pallas"

    def __init__(
        self,
        block_t: Optional[int] = None,
        block_rows: Optional[int] = None,
        block_s: Optional[int] = None,
        interpret: Optional[bool] = None,
    ):
        if interpret is None:
            platform = jax.default_backend()
            if platform != "tpu":
                raise RuntimeError(
                    f"Pallas kernels compile for TPU, and JAX's default "
                    f"backend is {platform!r}; pass interpret=True to run "
                    f"them in interpret mode"
                )
            interpret = False
        self.block_t = block_t
        self.block_rows = block_rows
        self.block_s = block_s
        self.interpret = interpret

    def lagged_sums(self, x: jax.Array, max_lag: int) -> jax.Array:
        from ..kernels.window_stats import ops as ws

        return ws.lagged_sums(
            x, max_lag, block_t=self.block_t, interpret=self.interpret
        )

    def masked_lagged_sums(
        self, y_padded: jax.Array, start_mask: jax.Array, max_lag: int
    ) -> jax.Array:
        from ..kernels.window_stats import ops as ws

        return ws.masked_lagged_sums(
            y_padded, start_mask, max_lag, block_t=self.block_t, interpret=self.interpret
        )

    def windowed_moments(self, x: jax.Array, window: int) -> jax.Array:
        from ..kernels.window_stats import ops as ws

        return ws.windowed_moments(
            x, window, block_t=self.block_t, interpret=self.interpret
        )

    def segment_fft_power(
        self, segments: jax.Array, taper: jax.Array, detrend: bool = True
    ) -> jax.Array:
        from ..kernels.segment_dft import ops as sd

        return sd.segment_fft_power(
            segments,
            taper,
            detrend,
            block_s=self.block_s,
            interpret=self.interpret,
        )

    def banded_matvec(self, diags: jax.Array, x: jax.Array) -> jax.Array:
        from ..kernels.banded_matvec import ops as bmv

        d = diags.shape[0]
        lead = x.shape[:-1]
        # kernel contract is (d, nrhs): fold any leading batch axes into nrhs.
        xr = x.reshape(-1, d).T if lead else x
        y = bmv.banded_matvec(
            diags, xr, block_rows=self.block_rows, interpret=self.interpret
        )
        return y.T.reshape(*lead, d) if lead else y

    def fused_lagged_moments(
        self,
        y_padded: jax.Array,
        start_mask: jax.Array,
        max_lag: int,
        window: "int | tuple",
    ) -> tuple:
        from ..kernels.window_stats import ops as ws

        return ws.fused_lagged_moments(
            y_padded,
            start_mask,
            max_lag,
            window,
            block_t=self.block_t,
            interpret=self.interpret,
        )

    def segment_csd(
        self, segments: jax.Array, taper: jax.Array, detrend: bool = True
    ) -> jax.Array:
        from ..kernels.segment_dft import ops as sd

        return sd.segment_csd(
            segments,
            taper,
            detrend,
            block_s=self.block_s,
            interpret=self.interpret,
        )

    def fused_plan_update(
        self,
        y_padded: jax.Array,
        start_mask: jax.Array,
        z0: jax.Array,
        max_lag: int,
        windows: tuple = (),
        seg_lens: tuple = (),
        seg_steps: tuple = (),
        tapers: tuple = (),
        detrend: bool = True,
        stage_dtype: "str | None" = None,
    ) -> tuple:
        from ..kernels.fused_plan import ops as fp

        return fp.fused_plan_update(
            y_padded,
            start_mask,
            z0,
            max_lag,
            windows,
            seg_lens,
            seg_steps,
            tapers,
            detrend,
            stage_dtype=stage_dtype,
            block_t=self.block_t,
            interpret=self.interpret,
        )


class AutoBackend:
    """Per-call dispatch by crossover table, not a hard-coded constant.

    Each primitive routes to the Pallas tile kernel once its problem size
    (rows for the windowed contractions, banded dimension for the matvec,
    total staged samples S·L for the segment DFT) reaches that primitive's
    crossover threshold (`repro.core.calibrate`).  The table is resolved
    lazily at the first dispatch and never measured there: the table the
    user installed for this platform if one exists, else the built-in
    default table — which off-accelerator says "always jnp", since
    interpret-mode Pallas is a validation vehicle ~100× slower than XLA.
    The Pallas side is the registered ``"pallas"`` backend unless one is
    passed in.

    Inject or refresh the policy at runtime:

        get_backend("auto").set_table(calibrate())
    """

    name = "auto"

    def __init__(
        self,
        jnp_backend: Optional[JnpBackend] = None,
        pallas_backend: Optional[PallasBackend] = None,
        table=None,
    ):
        self._jnp = jnp_backend or JnpBackend()
        self._pallas_backend = pallas_backend
        self._table = table

    @property
    def _pallas(self) -> Backend:
        return self._pallas_backend or get_backend("pallas")

    @property
    def table(self):
        """The active `repro.core.calibrate.CalibrationTable` (resolving it
        on first access — installed table > built-in default)."""
        if self._table is None:
            from .calibrate import resolve_table

            self._table = resolve_table()
        return self._table

    def set_table(self, table) -> None:
        """Swap the crossover table (e.g. a fresh ``calibrate()`` result).

        Also installs it as the process-wide active table so the kernels'
        tile-size resolution (`repro.kernels.tiling.resolve_block`) sees the
        same calibration artifact the dispatch policy uses.
        """
        self._table = table
        from .calibrate import set_active_table

        set_active_table(table)

    def _pick(self, primitive: str, size: int) -> Backend:
        if size >= self.table.crossover(primitive):
            return self._pallas
        return self._jnp

    def lagged_sums(self, x: jax.Array, max_lag: int) -> jax.Array:
        return self._pick("lagged_sums", x.shape[0]).lagged_sums(x, max_lag)

    def masked_lagged_sums(
        self, y_padded: jax.Array, start_mask: jax.Array, max_lag: int
    ) -> jax.Array:
        return self._pick(
            "masked_lagged_sums", start_mask.shape[0]
        ).masked_lagged_sums(y_padded, start_mask, max_lag)

    def windowed_moments(self, x: jax.Array, window: int) -> jax.Array:
        return self._pick("windowed_moments", x.shape[0]).windowed_moments(
            x, window
        )

    def segment_fft_power(
        self, segments: jax.Array, taper: jax.Array, detrend: bool = True
    ) -> jax.Array:
        staged = segments.shape[0] * segments.shape[1]
        return self._pick("segment_fft_power", staged).segment_fft_power(
            segments, taper, detrend
        )

    def banded_matvec(self, diags: jax.Array, x: jax.Array) -> jax.Array:
        return self._pick("banded_matvec", diags.shape[0]).banded_matvec(
            diags, x
        )

    def fused_lagged_moments(
        self,
        y_padded: jax.Array,
        start_mask: jax.Array,
        max_lag: int,
        window: "int | tuple",
    ) -> tuple:
        return self._pick(
            "fused_lagged_moments", start_mask.shape[0]
        ).fused_lagged_moments(y_padded, start_mask, max_lag, window)

    def segment_csd(
        self, segments: jax.Array, taper: jax.Array, detrend: bool = True
    ) -> jax.Array:
        staged = segments.shape[0] * segments.shape[1]
        return self._pick("segment_csd", staged).segment_csd(
            segments, taper, detrend
        )

    def fused_plan_update(
        self,
        y_padded: jax.Array,
        start_mask: jax.Array,
        z0: jax.Array,
        max_lag: int,
        windows: tuple = (),
        seg_lens: tuple = (),
        seg_steps: tuple = (),
        tapers: tuple = (),
        detrend: bool = True,
        stage_dtype: "str | None" = None,
    ) -> tuple:
        # A cached table measured before this primitive existed simply has
        # no entry — CalibrationTable.crossover falls back to the built-in
        # platform default (never a KeyError), so stale caches degrade to
        # the reasoned policy instead of crashing the fused-plan hot path.
        return self._pick(
            "fused_plan_update", start_mask.shape[0]
        ).fused_plan_update(
            y_padded,
            start_mask,
            z0,
            max_lag,
            windows,
            seg_lens,
            seg_steps,
            tapers,
            detrend,
            stage_dtype=stage_dtype,
        )


class CircuitBreakerBackend:
    """Self-healing dispatch: quarantine a raising primitive, keep serving.

    Wraps a ``primary`` backend (default: the registered ``"pallas"``) and
    a ``fallback`` oracle (default: jnp).  Each primitive carries its own breaker:

      * **closed** (healthy): dispatch goes to the primary.  A primary
        raise — a kernel build failure, an injected
        ``backend.<primitive>`` fault (`repro.runtime.chaos`) — is caught,
        the call is transparently served by the fallback, and after
        ``trip_after`` consecutive failures the breaker **opens**;
      * **open** (quarantined): the next ``cooldown_calls`` dispatches of
        that primitive go straight to the fallback — the primary is not
        even attempted, so a wedged kernel build can't stall serving;
      * **half-open** (probing): once the cooldown is spent, one dispatch
        probes the primary again.  Success closes the breaker (recovery);
        failure re-opens it for another cooldown.

    Every trip/recovery/fallback is recorded per primitive
    (:meth:`breaker_metrics`) — `repro.serving.gateway.StatsGateway
    .health` surfaces them when the served session runs on a breaker.

    The cooldown is counted in *dispatch calls*, not wall time, so chaos
    schedules replay deterministically.  Note primitive dispatch happens at
    trace time: a jit program that compiled against the fallback keeps
    using it for its shapes until re-traced — recovery applies to new
    traces, which is exactly the safe direction (never resurrect a raising
    kernel inside a cached program).
    """

    name = "breaker"

    def __init__(
        self,
        primary: Optional[Backend] = None,
        fallback: Optional[Backend] = None,
        trip_after: int = 1,
        cooldown_calls: int = 8,
    ):
        if trip_after < 1 or cooldown_calls < 1:
            raise ValueError("trip_after and cooldown_calls must be >= 1")
        self._primary = primary if primary is not None else get_backend("pallas")
        self._fallback = fallback if fallback is not None else JnpBackend()
        self.trip_after = trip_after
        self.cooldown_calls = cooldown_calls
        self._state: Dict[str, dict] = {}

    def _st(self, primitive: str) -> dict:
        st = self._state.get(primitive)
        if st is None:
            st = self._state[primitive] = {
                "state": "closed",
                "consecutive_failures": 0,
                "cooldown_left": 0,
                "trips": 0,
                "recoveries": 0,
                "probes": 0,
                "primary_calls": 0,
                "fallback_calls": 0,
                "last_error": None,
            }
        return st

    def _dispatch(self, primitive: str, *args, **kwargs):
        from ..runtime import chaos

        st = self._st(primitive)
        if st["state"] == "open":
            st["cooldown_left"] -= 1
            if st["cooldown_left"] > 0:
                st["fallback_calls"] += 1
                return getattr(self._fallback, primitive)(*args, **kwargs)
            st["state"] = "half-open"   # cooldown spent: this call probes
            st["probes"] += 1
        try:
            chaos.fire(f"backend.{primitive}")
            out = getattr(self._primary, primitive)(*args, **kwargs)
        except Exception as e:
            st["consecutive_failures"] += 1
            st["last_error"] = repr(e)
            if (
                st["state"] == "half-open"
                or st["consecutive_failures"] >= self.trip_after
            ):
                if st["state"] == "closed":
                    st["trips"] += 1   # count closed→open transitions only
                st["state"] = "open"
                st["cooldown_left"] = self.cooldown_calls
            st["fallback_calls"] += 1
            return getattr(self._fallback, primitive)(*args, **kwargs)
        if st["state"] == "half-open":
            st["recoveries"] += 1
        st["state"] = "closed"
        st["consecutive_failures"] = 0
        st["primary_calls"] += 1
        return out

    def __getattr__(self, name: str):
        # one wrapper per primitive, lazily bound — a new primitive added
        # to the protocol is covered without touching the breaker
        if name in PRIMITIVE_NAMES:
            fn = functools.partial(self._dispatch, name)
            object.__setattr__(self, name, fn)  # cache for later lookups
            return fn
        raise AttributeError(
            f"{type(self).__name__!s} object has no attribute {name!r}"
        )

    def breaker_metrics(self) -> dict:
        """Per-primitive breaker state plus totals: trips, recoveries,
        probes, primary/fallback call counts, last primary error."""
        per = {k: dict(v) for k, v in sorted(self._state.items())}
        return {
            "primitives": per,
            "trips": sum(v["trips"] for v in per.values()),
            "recoveries": sum(v["recoveries"] for v in per.values()),
            "fallback_calls": sum(v["fallback_calls"] for v in per.values()),
            "open": sorted(
                k for k, v in per.items() if v["state"] != "closed"
            ),
        }

    def reset(self, primitive: Optional[str] = None) -> None:
        """Operator override: forget breaker state (one primitive or all)."""
        if primitive is None:
            self._state.clear()
        else:
            self._state.pop(primitive, None)


# "pallas" is built at its first lookup (None until then): a compiled
# PallasBackend exists only on the TPU, and off it a caller that wants the
# kernels registers an interpret-mode instance first.
_REGISTRY: Dict[str, Optional[Backend]] = {
    "jnp": JnpBackend(),
    "pallas": None,
    "auto": AutoBackend(),
}
_DEFAULT = "auto"


def register_backend(name: str, backend: Backend) -> None:
    """Add (or replace) a named backend — the one place a new substrate
    (GPU Triton, CPU-vectorized, …) plugs into every estimator at once."""
    _REGISTRY[name] = backend


def list_backends() -> tuple:
    return tuple(sorted(_REGISTRY))


def set_default_backend(name: str) -> None:
    """Change what ``backend=None`` resolves to (deployment-wide policy)."""
    global _DEFAULT
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; registered: {list_backends()}")
    _DEFAULT = name


def get_backend(spec: BackendSpec = None) -> Backend:
    """Resolve ``backend=`` arguments: None → default, str → registry lookup,
    Backend instance → itself."""
    if spec is None:
        spec = _DEFAULT
    if isinstance(spec, str):
        if spec not in _REGISTRY:
            raise KeyError(
                f"unknown backend {spec!r}; registered: {list_backends()}"
            )
        if _REGISTRY[spec] is None:
            _REGISTRY[spec] = PallasBackend()
        return _REGISTRY[spec]
    return spec
