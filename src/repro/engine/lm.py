"""The LM workload behind the unified session surface.

    cfg = SessionConfig(backend="pallas-lm", autotune=True,
                        lm=LMConfig(arch="gemma3-4b", max_context=64,
                                    decode_batch=4))
    sess = LMSession(config=cfg)
    tokens = sess.generate(prompts, max_new=16)   # greedy, (B, 16) int32

:class:`LMSession` shares every piece of engine machinery the CNN
session uses — :class:`SessionConfig` (with its ``lm`` sub-config), the
backend registry (the ``"pallas-lm"`` entry), and the on-disk
:class:`TuningCache` (Pallas kernel variants are timed candidates
exactly like C unroll levels; see
:func:`repro.engine.autotune.tune_lm_variants`).  A config with
``lm.mesh_shape`` set serves data-parallel prefill through
:class:`repro.launch.sharding.MeshPar`; a host with fewer devices than
the mesh needs is an error, never a silent single-device run.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .autotune import LMTuneResult, TuningCache, tune_lm_variants
from .backends import KVCacheHandle, LMBackend, get_backend
from .config import LMConfig, SessionConfig
from .session import SessionInfo


class LMSession:
    """Build once, prefill/decode many — over any registered LM backend.

    Parameters
    ----------
    config:  a :class:`SessionConfig` with ``lm`` set (also accepts a
             bare :class:`LMConfig`, or a dict for either).  The default
             CNN backend ``"c"`` is upgraded to ``"pallas-lm"``; naming
             a non-LM backend explicitly is an error.
    params:  optional parameter pytree (defaults to a seeded
             ``init_params`` of the arch — the deterministic CI path).
    mesh:    optional pre-built jax mesh; otherwise ``lm.mesh_shape``
             (when set) builds one, and raises ``ValueError`` when the
             host has too few devices.
    """

    def __init__(self, config=None, *, params=None, mesh=None):
        if config is None:
            config = SessionConfig(backend="pallas-lm", lm=LMConfig())
        if isinstance(config, LMConfig):
            config = SessionConfig(backend="pallas-lm", lm=config)
        if isinstance(config, dict):
            config = SessionConfig(**config)
        if config.lm is None:
            raise TypeError(
                "LMSession needs SessionConfig.lm (an LMConfig); for CNN "
                "graphs use InferenceSession")
        if config.backend == "c":  # the SessionConfig default, not a choice
            config = config.replace(backend="pallas-lm")
        self.config = config
        self.backend_name = config.backend
        lm = config.lm

        backend_cls = get_backend(config.backend)
        if not issubclass(backend_cls, LMBackend):
            raise ValueError(
                f"backend {config.backend!r} does not implement the LM "
                f"contract (prefill/decode); it serves CNN graphs")

        from repro.configs.lm_archs import ARCHS
        model_cfg = ARCHS[lm.arch]
        if lm.smoke:
            model_cfg = model_cfg.smoke()
        self.model_cfg = model_cfg

        self.mesh = mesh
        if self.mesh is None and lm.mesh_shape is not None:
            self.mesh = self._make_mesh(lm.mesh_shape)
        par = None
        if self.mesh is not None:
            from repro.launch.sharding import MeshPar
            par = MeshPar(self.mesh, model_cfg)

        if params is None:
            import jax
            from repro.models.lm import init_params
            params = init_params(model_cfg, jax.random.PRNGKey(lm.seed))

        # kernel policy: axes the LMConfig pins are fixed; the rest are
        # autotuned (winner persisted) or left at the defaults
        fixed = {}
        if lm.attn_variant is not None:
            fixed["attention"] = lm.attn_variant
        if lm.scan_variant is not None:
            fixed["scan"] = lm.scan_variant
        if lm.block_q is not None:
            fixed["block_q"] = int(lm.block_q)
        if lm.block_k is not None:
            fixed["block_k"] = int(lm.block_k)
        self.tuned: Optional[LMTuneResult] = None
        if config.autotune:
            self.tuned = tune_lm_variants(
                model_cfg, params,
                max_context=lm.max_context,
                batch=lm.decode_batch,
                prompt=min(16, lm.max_context),
                cache=self._tuning_cache(),
                iters=max(1, config.tune_iters // 100),
                fixed=fixed, par=par)
            policy = self.tuned.policy
        else:
            from repro.models.kernel_policy import DEFAULT_KERNELS
            policy = DEFAULT_KERNELS._replace(**fixed).validate()

        self._backend: LMBackend = backend_cls(
            model_cfg, params=params, max_context=lm.max_context,
            decode_batch=lm.decode_batch, policy=policy, par=par,
            seed=lm.seed)
        self.kernel_policy = self._backend.policy

    @staticmethod
    def _make_mesh(shape):
        """Build the requested mesh; raise when the host has too few
        devices for it."""
        import math

        import jax

        from repro.launch.mesh import make_mesh
        need = math.prod(shape)
        have = len(jax.devices())
        if need > have:
            raise ValueError(
                f"lm.mesh_shape {tuple(shape)} needs {need} devices but "
                f"the host has {have}")
        return make_mesh(shape)

    def _tuning_cache(self) -> TuningCache:
        tc = self.config.tune_cache
        return tc if isinstance(tc, TuningCache) else TuningCache(tc)

    # -- execution -----------------------------------------------------------

    def prefill(self, tokens: np.ndarray):
        """``(B, T)`` int32 prompts -> ``(last_logits, KVCacheHandle)``."""
        return self._backend.prefill(tokens)

    def decode(self, handle: KVCacheHandle, tokens: np.ndarray) -> np.ndarray:
        """One greedy-loop step: ``(B,)`` tokens -> ``(B, V)`` logits."""
        return self._backend.decode(handle, tokens)

    def generate(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        """Greedy decode: ``(B, T)`` int32 -> ``(B, max_new)`` int32."""
        return self._backend.generate(prompts, max_new)

    def predict(self, tokens: np.ndarray) -> np.ndarray:
        """Full-sequence logits ``(B, T)`` -> ``(B, T, V)`` (the
        ``predict_batch`` face of the shared Backend contract)."""
        return self._backend.predict_batch(tokens)

    @property
    def backend(self) -> LMBackend:
        return self._backend

    def close(self) -> None:
        self._backend.close()

    # -- introspection -------------------------------------------------------

    @property
    def info(self) -> SessionInfo:
        d = SessionInfo(
            backend=self.backend_name,
            workload="lm",
            arch=self.model_cfg.name,
            kernel_policy=dict(self.kernel_policy._asdict()),
            config=self.config.to_dict())
        if self.tuned is not None:
            d.update(tuned_prefill_us=self.tuned.prefill_us,
                     tuned_from_cache=self.tuned.from_cache)
        d.update(self._backend.describe())
        return d
