"""Typed, frozen session configuration.

:class:`InferenceSession` grew one keyword argument per PR until
constructing it programmatically (the serving layer, benchmark sweeps,
config files) meant threading seventeen loosely-validated kwargs.
:class:`SessionConfig` is the consolidation: one frozen dataclass, one
nested :class:`CalibrationConfig` for the int8 calibration knobs,
validation at construction time, and a stable JSON-safe ``to_dict()``
that round-trips::

    cfg = SessionConfig(backend="c", autotune=True, precision="int8")
    sess = InferenceSession(graph, config=cfg)
    assert SessionConfig(**sess.info["config"]) == cfg.portable()

The legacy per-kwarg path (``InferenceSession(graph, backend="c",
autotune=True, ...)``) still works through a deprecation shim in
``session.py`` that builds a ``SessionConfig`` internally.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple, Union

from repro.core import quantize as quantize_mod

_PRECISIONS = ("fp32", "int8")


@dataclass(frozen=True)
class CalibrationConfig:
    """The int8 calibration knobs (ignored at ``precision="fp32"``).

    ``data`` is the representative sample batch ``(N, *in_shape)``; when
    ``None`` the session synthesizes ``samples`` camera-like frames via
    :func:`repro.data.pipeline.camera_frame_batch` (bounded, spatially
    smooth — the input domain the paper's nets actually see).  ``data``
    is runtime state, not a knob: it is excluded from ``to_dict()``.

    ``method=None`` means *auto*: ``"minmax"`` when the caller provided
    ``data`` (the historical, bit-stable behavior), ``"percentile"``
    when the session synthesizes its default frames (outlier-tail clip
    is what keeps the robot net's top-1 agreement >= 0.99 there).

    ``qparams`` accepts externally-determined quantization parameters —
    e.g. exported from a QAT run — as a mapping of layer name to
    :class:`repro.core.quantize.QParams` (or a ``(scale, zero_point)``
    pair).  When set, the session skips calibration entirely and feeds
    the provided scales/zero-points straight into the
    :class:`QuantizedGraph`; like ``data`` it is runtime state, not a
    serializable knob.

    ``per_channel=True`` gives eligible layers per-output-channel
    activation qparams (scales folded into the consumers' weight
    quantization; see :func:`repro.core.quantize.per_channel_eligible`)
    — finer steps for narrow channels at zero inner-loop cost.
    Ignored when ``qparams`` is provided (the import format is
    per-tensor).
    """

    data: Optional[Any] = None          # np.ndarray; not serialized
    samples: int = 32
    method: Optional[str] = None        # None = auto (see above)
    percentile: float = 99.99
    qparams: Optional[Dict[str, Any]] = None  # QAT import; not serialized
    per_channel: bool = False

    def __post_init__(self):
        if (self.method is not None
                and self.method not in quantize_mod.CALIBRATION_METHODS):
            raise ValueError(
                f"calibration method {self.method!r}; expected one of "
                f"{quantize_mod.CALIBRATION_METHODS} or None (auto)")
        if not (0.0 < self.percentile <= 100.0):
            raise ValueError(
                f"calibration percentile {self.percentile!r} not in (0, 100]")
        if self.samples < 1:
            raise ValueError(f"calibration samples {self.samples} < 1")

    def resolved_method(self, *, data_provided: bool) -> str:
        """The concrete range-selection method after resolving auto."""
        if self.method is not None:
            return self.method
        return "minmax" if data_provided else "percentile"

    def to_dict(self) -> Dict[str, Any]:
        """JSON-safe knobs (``data`` omitted — arrays don't serialize)."""
        return {"samples": self.samples, "method": self.method,
                "percentile": self.percentile,
                "per_channel": self.per_channel}


@dataclass(frozen=True)
class LMConfig:
    """The LM workload sub-config carried by ``SessionConfig.lm``.

    Setting it routes the session through :class:`repro.engine.lm.LMSession`
    and the ``"pallas-lm"`` backend instead of a compiled CNN graph.

    ``arch`` names an entry of :data:`repro.configs.lm_archs.ARCHS`;
    ``smoke=True`` shrinks it via ``ModelConfig.smoke()`` (the CI/CPU
    shape).  ``attn_variant``/``scan_variant``/``block_q``/``block_k``
    pin :class:`repro.models.kernel_policy.KernelPolicy` axes; axes left
    ``None`` are chosen by the autotuner when ``autotune=True`` (winner
    persisted in the tuning cache) and fall back to the defaults
    otherwise.  ``mesh_shape`` requests a device mesh for data-parallel
    prefill via :mod:`repro.launch.mesh`; when the host has fewer
    devices the session raises ``ValueError``.
    """

    arch: str = "gemma3-4b"
    smoke: bool = True
    max_context: int = 128
    decode_batch: int = 1
    attn_variant: Optional[str] = None
    scan_variant: Optional[str] = None
    block_q: Optional[int] = None
    block_k: Optional[int] = None
    mesh_shape: Optional[Tuple[int, ...]] = None
    seed: int = 0

    def __post_init__(self):
        # deferred imports: repro.configs/repro.models pull in jax, which
        # the pure-C config path must not require at import time
        from repro.configs.lm_archs import ARCHS
        from repro.models.kernel_policy import (ATTENTION_VARIANTS,
                                                SCAN_VARIANTS)
        if self.arch not in ARCHS:
            raise ValueError(
                f"lm arch {self.arch!r}; expected one of "
                f"{tuple(sorted(ARCHS))}")
        if self.max_context < 1:
            raise ValueError(f"max_context {self.max_context} < 1")
        if self.decode_batch < 1:
            raise ValueError(f"decode_batch {self.decode_batch} < 1")
        if (self.attn_variant is not None
                and self.attn_variant not in ATTENTION_VARIANTS):
            raise ValueError(
                f"attn_variant {self.attn_variant!r}; expected one of "
                f"{ATTENTION_VARIANTS} or None (autotuned)")
        if (self.scan_variant is not None
                and self.scan_variant not in SCAN_VARIANTS):
            raise ValueError(
                f"scan_variant {self.scan_variant!r}; expected one of "
                f"{SCAN_VARIANTS} or None (autotuned)")
        for name in ("block_q", "block_k"):
            v = getattr(self, name)
            if v is not None and v < 1:
                raise ValueError(f"{name} {v} < 1")
        if self.mesh_shape is not None:
            object.__setattr__(self, "mesh_shape",
                               tuple(int(d) for d in self.mesh_shape))
            if any(d < 1 for d in self.mesh_shape):
                raise ValueError(f"mesh_shape {self.mesh_shape}")

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        if d["mesh_shape"] is not None:
            d["mesh_shape"] = list(d["mesh_shape"])
        return d


def _coerce_lm(v) -> Optional[LMConfig]:
    if v is None or isinstance(v, LMConfig):
        return v
    if isinstance(v, dict):
        return LMConfig(**v)
    if isinstance(v, str):  # shorthand: lm="gemma3-4b"
        return LMConfig(arch=v)
    raise TypeError(f"lm must be an LMConfig, dict, arch name or None; "
                    f"got {type(v).__name__}")


def _coerce_calibration(v) -> CalibrationConfig:
    if isinstance(v, CalibrationConfig):
        return v
    if isinstance(v, dict):
        return CalibrationConfig(**v)
    if v is None:
        return CalibrationConfig()
    # legacy spelling: calibration=<sample batch array>
    return CalibrationConfig(data=v)


@dataclass(frozen=True)
class SessionConfig:
    """Everything :class:`InferenceSession` needs beyond the graph.

    Field semantics match the historical kwargs one-for-one (see the
    session docstring); the four calibration knobs live in the nested
    :class:`CalibrationConfig`.  Frozen: a config can key caches and be
    shared across threads/workers without defensive copies.
    """

    backend: str = "c"
    autotune: bool = False
    simd: Optional[str] = None
    simd_search: Optional[Tuple[str, ...]] = None
    unroll: Union[str, int, None, Dict] = "auto"
    optimize: bool = True
    threads: Optional[int] = None
    tune_cache: Optional[Any] = None    # dir path str, or a TuningCache
    tune_iters: int = 300
    func_name: str = "nncg_net"
    precision: str = "fp32"
    calibration: CalibrationConfig = field(default_factory=CalibrationConfig)
    # graph-level schedule (C backend): epilogue fusion on/off for
    # every consumer kind — residual Adds, MaxPool/AvgPool, Concat
    # edges (None = auto = on, and int8 autotune additionally times
    # kind subsets as code variants; output is bitwise identical
    # either way) and pipeline stage count (1 = monolithic, k>1 =
    # layer-pipelined build streaming batches across k cores, 0 =
    # auto: the autotuner times the host's viable stage counts and
    # keeps the fastest)
    fusion: Optional[bool] = None
    pipeline_stages: int = 1
    # LM workload sub-config; None = classic CNN-graph session.  Accepts
    # an LMConfig, a dict (from to_dict round-trips), or an arch name.
    lm: Optional[LMConfig] = None

    def __post_init__(self):
        if self.precision not in _PRECISIONS:
            raise ValueError(
                f"precision {self.precision!r}; expected one of {_PRECISIONS}")
        if self.tune_iters < 1:
            raise ValueError(f"tune_iters {self.tune_iters} < 1")
        if self.pipeline_stages < 0:
            raise ValueError(
                f"pipeline_stages {self.pipeline_stages} < 0 "
                f"(0 = auto, 1 = single stage, k = k stages)")
        # normalize the container-ish fields so equality and to_dict()
        # are stable regardless of how the caller spelled them
        object.__setattr__(self, "calibration",
                           _coerce_calibration(self.calibration))
        object.__setattr__(self, "lm", _coerce_lm(self.lm))
        if self.simd_search is not None:
            object.__setattr__(self, "simd_search",
                               tuple(self.simd_search))

    def replace(self, **changes) -> "SessionConfig":
        """A copy with ``changes`` applied (frozen-friendly update)."""
        return dataclasses.replace(self, **changes)

    def portable(self) -> "SessionConfig":
        """The serializable projection of this config: calibration data
        and live :class:`TuningCache` objects dropped (a cache *path*
        string is kept).  ``SessionConfig(**cfg.to_dict())`` equals
        ``cfg.portable()``."""
        changes: Dict[str, Any] = {}
        if (self.calibration.data is not None
                or self.calibration.qparams is not None):
            changes["calibration"] = dataclasses.replace(
                self.calibration, data=None, qparams=None)
        if self.tune_cache is not None and not isinstance(
                self.tune_cache, str):
            changes["tune_cache"] = getattr(self.tune_cache, "path", None)
        return self.replace(**changes) if changes else self

    def to_dict(self) -> Dict[str, Any]:
        """Stable JSON-safe dict; ``SessionConfig(**d)`` reconstructs."""
        p = self.portable()
        d = dataclasses.asdict(p)
        d["calibration"] = p.calibration.to_dict()
        d["lm"] = p.lm.to_dict() if p.lm is not None else None
        if d["simd_search"] is not None:
            d["simd_search"] = list(d["simd_search"])
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SessionConfig":
        return cls(**d)
