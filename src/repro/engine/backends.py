"""Backend registry for the unified inference engine.

One trained :class:`~repro.core.graph.CNNGraph`, three execution
substrates — the paper's deployment artifact plus its two baselines:

* ``"c"``      — NNCG-generated ANSI C, compiled with the host ``cc``
  and loaded via ctypes (the paper's shipped path).
* ``"xla"``    — ``jax.jit`` of the reference forward (the modern
  equivalent of the paper's TF-XLA rival); batches go through a
  ``vmap``'d single-image oracle.
* ``"pallas"`` — the Pallas TPU kernels (Mosaic on the TPU, interpret
  mode on the CPU backend; any other platform raises).

``Backend`` is a formal ABC, not duck typing: every substrate
implements ``predict_batch`` and inherits ``describe()`` (a stable
dict of what this backend is), ``close()`` (release native resources;
default no-op), and ``worker()`` (a reentrant execution handle for
server worker pools — see :mod:`repro.serve`).  New substrates
register with :func:`register_backend`; the engine and every caller
dispatch purely by name through :func:`get_backend`.
"""
from __future__ import annotations

import abc
import ctypes
import time
from dataclasses import replace
from typing import Dict, List, Optional, Type

import numpy as np

from repro.core import cgen, jax_exec, runtime
from repro.core.graph import CNNGraph

_REGISTRY: Dict[str, Type["Backend"]] = {}


def register_backend(name: str):
    """Class decorator: make a backend constructible by name."""

    def deco(cls: Type["Backend"]) -> Type["Backend"]:
        if not (isinstance(cls, type) and issubclass(cls, Backend)):
            raise TypeError(
                f"register_backend({name!r}): {cls!r} must subclass Backend")
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def get_backend(name: str) -> Type["Backend"]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


class Backend(abc.ABC):
    """One execution substrate — the engine's formal serving interface.

    Constructed with an *optimized* graph (passes already applied).
    Required: :meth:`predict_batch` maps ``(N, *in_shape)`` float32 to
    ``(N, *out_shape)`` float32.  Optional overrides: :meth:`describe`
    (extend the base dict with substrate facts), :meth:`close` (release
    native resources), :meth:`worker` (hand a server worker a handle it
    may call concurrently with other workers' handles).
    """

    name = "?"
    precision = "fp32"
    workload = "cnn"

    def __init__(self, graph: Optional[CNNGraph]):
        # LM backends (workload="lm") have no CNNGraph; everything that
        # reads .graph/.out_shape must tolerate None for them.
        self.graph = graph
        self.out_shape = graph.output_shape if graph is not None else None

    @abc.abstractmethod
    def predict_batch(self, x: np.ndarray) -> np.ndarray:
        """``(N, *in_shape)`` float32 -> ``(N, *out_shape)`` float32."""

    def describe(self) -> dict:
        """Stable facts about this backend (extended by subclasses)."""
        return {
            "name": self.name,
            "precision": self.precision,
            "input_shape": tuple(self.graph.input_shape),
            "output_shape": tuple(self.out_shape),
        }

    def close(self) -> None:
        """Release backend resources. Idempotent; default no-op."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def worker(self) -> "Backend":
        """An execution handle a server worker thread may use
        concurrently with other workers' handles.  Substrates whose
        ``predict_batch`` is already reentrant (jit-compiled jax
        functions) return ``self``; substrates with per-call scratch
        state (the C arena) return a handle owning private scratch."""
        return self

    def time_per_call_us(self, x: np.ndarray, iters: int = 500,
                         warmup: int = 20) -> float:
        """Single-image latency, mean over ``iters`` calls, in µs."""
        xb = np.ascontiguousarray(x[None], dtype=np.float32)
        for _ in range(warmup):
            self.predict_batch(xb)
        t0 = time.perf_counter()
        for _ in range(iters):
            self.predict_batch(xb)
        return (time.perf_counter() - t0) / iters * 1e6


class _CArenaWorker(Backend):
    """A per-thread handle on a compiled net: one warm liveness-planned
    workspace, driven through the reentrant ``<func>_ws`` entry.  Many
    of these can run concurrently against the same ``.so`` — ctypes
    releases the GIL during the call."""

    name = "c-worker"

    def __init__(self, parent: "CBackend"):
        super().__init__(parent.graph)
        self.name = parent.name + "-worker"
        self.precision = parent.precision
        self._net = parent.net
        self._ws = self._net._alloc_workspace()
        self._wp = self._ws.ctypes.data_as(
            ctypes.POINTER(self._net._ws_ctype))

    def predict_batch(self, x: np.ndarray) -> np.ndarray:
        net = self._net
        x = np.ascontiguousarray(x, dtype=np.float32)
        n = x.size // net.in_size
        if net._stage_fns and n > 1:
            # layer-pipelined build: stream the batch stage-overlapped
            # (the runner allocates its own buffers — reentrant across
            # concurrent server workers)
            return net.predict_batch(x).reshape((n,) + self.out_shape)
        out = np.empty(n * net.out_size, dtype=np.float32)
        FLOATP = ctypes.POINTER(ctypes.c_float)
        if net._batch_ws_fn is not None:
            # the whole batch in one GIL-releasing foreign call
            net._batch_ws_fn(x.ctypes.data_as(FLOATP),
                             out.ctypes.data_as(FLOATP),
                             ctypes.c_int(n), self._wp)
            return out.reshape((n,) + self.out_shape)
        xf = x.reshape(-1)
        for b in range(n):
            xi = xf[b * net.in_size:(b + 1) * net.in_size]
            oi = out[b * net.out_size:(b + 1) * net.out_size]
            net._ws_fn(xi.ctypes.data_as(FLOATP),
                       oi.ctypes.data_as(FLOATP), self._wp)
        return out.reshape((n,) + self.out_shape)


@register_backend("c")
class CBackend(Backend):
    """NNCG: graph -> C -> cc -> ctypes. Batches run through the
    generated ``<func>_batch`` loop wrapper, or — with ``threads>1`` —
    thread-parallel over the reentrant ``<func>_ws`` workspace entry
    (each thread owns one liveness-planned arena).

    Passing ``qgraph`` (a calibrated
    :class:`repro.core.quantize.QuantizedGraph`) selects the int8
    codegen path: int8 weights/intermediates, int32 accumulators, a
    byte-planned arena, float32 in/out — same serving interface."""

    def __init__(self, graph: CNNGraph, *, simd: str = "sse",
                 unroll=0, func_name: str = "nncg_net",
                 term_budget: Optional[int] = None,
                 threads: Optional[int] = None,
                 qgraph=None, schedule=None):
        super().__init__(graph)
        kw = {} if term_budget is None else {"term_budget": term_budget}
        self.opts = cgen.CodegenOptions(simd=simd, unroll=unroll,
                                        func_name=func_name, **kw)
        self.threads = threads
        self.qgraph = qgraph
        self.schedule = schedule
        if qgraph is not None:
            self.precision = "int8"
            self.net = runtime.build_quantized(qgraph, self.opts,
                                               schedule=schedule)
        else:
            self.net = runtime.build(graph, self.opts, schedule=schedule)
        if self.net.simd != self.opts.simd:
            # the runtime CPU-feature guard demoted the requested
            # variant; report what actually runs
            self.opts = replace(self.opts, simd=self.net.simd)

    def predict_batch(self, x: np.ndarray) -> np.ndarray:
        n = x.shape[0]
        out = self.net.predict_batch(x, threads=self.threads)
        return out.reshape((n,) + self.out_shape)

    def describe(self) -> dict:
        d = super().describe()
        d.update(simd=self.opts.simd, threads=self.threads,
                 so_path=self.net.so_path,
                 c_source_bytes=self.net.c_source_bytes,
                 arena_bytes=self.net.arena_bytes,
                 arena_buffer_sum_bytes=self.net.arena_buffer_sum_bytes,
                 per_layer_live_bytes=dict(
                     self.net.per_layer_live_bytes or {}),
                 pipeline_stages=self.net.nstages,
                 schedule_digest=self.net.schedule_digest)
        return d

    def worker(self) -> Backend:
        if self.net._ws_fn is None:  # pre-arena .so: not reentrant
            return self
        return _CArenaWorker(self)

    def time_per_call_us(self, x: np.ndarray, iters: int = 500,
                         warmup: int = 20) -> float:
        # ctypes-level loop: excludes Python dispatch, like the paper's
        # in-process measurement. One image only — a batch here would
        # silently time just its first image.
        assert x.size == self.net.in_size, (
            f"time_per_call_us expects one image of {self.graph.input_shape}, "
            f"got {x.shape}")
        return self.net.time_per_call_us(x, iters=iters, warmup=warmup)


class _JaxBackend(Backend):
    """Shared plumbing for the jit-compiled substrates."""

    def _make_fn(self, graph: CNNGraph):
        raise NotImplementedError

    def __init__(self, graph: CNNGraph):
        super().__init__(graph)
        self._fn = self._make_fn(graph)

    def predict_batch(self, x: np.ndarray) -> np.ndarray:
        """One call in three profiler spans on the host's clock: the copy
        in issued, the jitted call (returns once the program is
        enqueued), and the wait for the answer with its copy back.
        Outside a profiler session the spans record nothing."""
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("nncg.to_device"):
            xd = jnp.asarray(x, jnp.float32)
        with TraceAnnotation("nncg.launch"):
            y = self._fn(xd)
        # one sync, in np.asarray: a separate wait before it costs the
        # host another wake-up per call (4 % of a batch-1 call on a v5e)
        with TraceAnnotation("nncg.to_host"):
            return np.asarray(y, np.float32).reshape(
                (x.shape[0],) + self.out_shape)

    def time_per_call_us(self, x: np.ndarray, iters: int = 500,
                         warmup: int = 20) -> float:
        import jax.numpy as jnp
        xb = jnp.asarray(x[None], jnp.float32)
        self._fn(xb).block_until_ready()
        for _ in range(warmup):
            self._fn(xb).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            self._fn(xb).block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e6


@register_backend("xla")
class XLABackend(_JaxBackend):
    """The paper's rival compiler stack: one XLA program per batch via a
    vmap'd single-image oracle."""

    def _make_fn(self, graph: CNNGraph):
        return jax_exec.make_vmap_forward(graph)


class QuantizedXLABackend(_JaxBackend):
    """XLA-compiled int8 reference
    (:func:`repro.core.jax_exec.forward_quantized`) — the parity oracle
    the quantized C build must match bit-for-bit on the integer path.
    Constructed directly by the session (not in the registry: it needs
    the calibrated ``QuantizedGraph``, not just a graph)."""

    name = "xla-int8"
    precision = "int8"

    def __init__(self, qgraph):
        self.qgraph = qgraph
        super().__init__(qgraph.graph)

    def _make_fn(self, graph: CNNGraph):
        return jax_exec.make_jit_forward_quantized(self.qgraph)


@register_backend("pallas")
class PallasBackend(_JaxBackend):
    """TPU-native deployment path: Mosaic kernels on the TPU, interpret
    mode on the CPU backend, an error on any other platform. Requires an
    optimized graph — BN folded, activations fused, no Dense/Flatten."""

    def _make_fn(self, graph: CNNGraph):
        import jax

        @jax.jit
        def f(x):
            return jax_exec.forward_pallas(graph, x)

        return f

    def describe(self) -> dict:
        """Adds the device the kernels run on, whether they run in
        interpret mode, and per layer ``"pallas"`` or ``"jnp"``."""
        import jax

        from repro.kernels import ops
        d = super().describe()
        dev = jax.devices()[0]
        d.update(platform=dev.platform, device_kind=dev.device_kind,
                 interpret=ops._default_interpret(),
                 layers=jax_exec.pallas_layer_plan(self.graph))
        return d


# =========================================================== LM workload ====

class KVCacheHandle:
    """An opaque decode-state handle: the per-layer KV/recurrence caches
    plus the next write position.  Returned by :meth:`LMBackend.prefill`,
    advanced in place by :meth:`LMBackend.decode` — the token-server and
    session layers never look inside."""

    __slots__ = ("caches", "pos", "batch")

    def __init__(self, caches, pos, batch: int):
        self.caches = caches
        self.pos = pos
        self.batch = batch

    def __repr__(self):
        return f"KVCacheHandle(batch={self.batch}, pos={self.pos})"


class LMBackend(Backend):
    """The LM execution contract next to ``predict_batch``: explicit
    prefill/decode steps over a :class:`KVCacheHandle`.

    ``predict_batch`` stays in the interface — for an LM it maps int32
    token ids ``(N, T)`` to full-sequence logits ``(N, T, V)`` — so the
    registry, the server worker pool and ``describe()`` plumbing treat
    both workloads identically; the token-level serving path uses the
    three LM methods below."""

    workload = "lm"

    @abc.abstractmethod
    def prefill(self, tokens: np.ndarray):
        """``(B, T)`` int32 prompts -> ``(last_logits (B, V),
        KVCacheHandle)``."""

    @abc.abstractmethod
    def decode(self, handle: KVCacheHandle, tokens: np.ndarray) -> np.ndarray:
        """One step: ``(B,)`` int32 tokens against ``handle`` ->
        ``(B, V)`` logits.  Advances the handle in place."""

    def generate(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        """Greedy decode: ``(B, T)`` int32 -> ``(B, max_new)`` int32."""
        prompts = np.asarray(prompts, np.int32)
        if max_new < 1:
            return np.zeros((prompts.shape[0], 0), np.int32)
        logits, handle = self.prefill(prompts)
        tok = np.argmax(logits, axis=-1).astype(np.int32)
        out = [tok]
        for _ in range(max_new - 1):
            logits = self.decode(handle, tok)
            tok = np.argmax(logits, axis=-1).astype(np.int32)
            out.append(tok)
        return np.stack(out, axis=1)


@register_backend("pallas-lm")
class PallasLMBackend(LMBackend):
    """The gemma3-style LM stack (:mod:`repro.models`) as a registry
    citizen: jit-compiled prefill/decode closed over a
    :class:`~repro.models.kernel_policy.KernelPolicy` (the autotuned
    Pallas-variant choice) and an optional :class:`MeshPar` for
    data-parallel prefill.  Constructed by
    :class:`repro.engine.lm.LMSession`, not from a ``CNNGraph``."""

    def __init__(self, model_cfg, *, params=None, max_context: int = 128,
                 decode_batch: int = 1, policy=None, par=None, seed: int = 0):
        import jax

        from repro.models import lm as lm_mod
        from repro.models.kernel_policy import DEFAULT_KERNELS
        from repro.models.stack import DEFAULT_PAR

        super().__init__(None)
        self.model_cfg = model_cfg
        self.max_context = int(max_context)
        self.decode_batch = int(decode_batch)
        base_par = DEFAULT_PAR if par is None else par
        self.par = base_par.with_kernels(policy)
        self.policy = getattr(self.par, "kernels", DEFAULT_KERNELS)
        self.mesh = getattr(base_par, "mesh", None)
        if params is None:
            params = lm_mod.init_params(model_cfg, jax.random.PRNGKey(seed))
        if self.mesh is not None:
            from repro.launch.sharding import param_specs, to_named
            params = jax.device_put(
                params, to_named(self.mesh, param_specs(self.mesh, params)))
        self.params = params
        self._prefill_fn = jax.jit(lm_mod.make_prefill_step(
            model_cfg, max_len=self.max_context, par=self.par))
        self._decode_fn = (None if model_cfg.is_encoder else jax.jit(
            lm_mod.make_decode_step(model_cfg, par=self.par)))

        def _full(p, tokens):
            logits, _ = lm_mod.forward(p, model_cfg, {"tokens": tokens},
                                       self.par)
            return logits

        self._forward_fn = jax.jit(_full)

    # ----------------------------------------------------- LM contract --
    def prefill(self, tokens: np.ndarray):
        import jax.numpy as jnp
        tokens = np.asarray(tokens, np.int32)
        B, T = tokens.shape
        if T > self.max_context:
            raise ValueError(
                f"prompt length {T} > max_context {self.max_context}")
        logits, caches, pos = self._prefill_fn(
            self.params, {"tokens": jnp.asarray(tokens)})
        return (np.asarray(logits, np.float32),
                KVCacheHandle(caches, pos, batch=B))

    def decode(self, handle: KVCacheHandle, tokens: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        if self._decode_fn is None:
            raise ValueError(
                f"{self.model_cfg.name} is encoder-only: no decode step")
        tokens = np.asarray(tokens, np.int32).reshape(handle.batch, 1)
        logits, handle.caches, handle.pos = self._decode_fn(
            self.params, handle.caches, jnp.asarray(tokens), handle.pos)
        return np.asarray(logits, np.float32)

    # ------------------------------------------------- shared contract --
    def predict_batch(self, x: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        tokens = jnp.asarray(np.asarray(x, np.int32))
        return np.asarray(self._forward_fn(self.params, tokens), np.float32)

    def describe(self) -> dict:
        from repro.models.lm import param_count
        return {
            "name": self.name,
            "precision": self.precision,
            "workload": self.workload,
            "arch": self.model_cfg.name,
            "vocab_size": self.model_cfg.vocab_size,
            "max_context": self.max_context,
            "decode_batch": self.decode_batch,
            "kernel_policy": dict(self.policy._asdict()),
            "n_params": param_count(self.model_cfg),
            "mesh": (None if self.mesh is None
                     else dict(zip(self.mesh.axis_names,
                                   [self.mesh.shape[a]
                                    for a in self.mesh.axis_names]))),
        }
