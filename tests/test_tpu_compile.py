"""The Pallas kernels of the main path compile for a TPU v5e.

Nothing runs: each kernel is compiled at its real shape for a chip that
is described, not attached, by the TPU compiler installed with JAX. This
catches what interpret mode cannot, such as a slice Mosaic refuses or a
block that does not fit VMEM. The topology is described inside a fixture,
so that only the worker running this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.cnn_paper import EXTRA_CNNS, PAPER_CNNS
from repro.core import jax_exec
from repro.core.graph import Conv2D
from repro.engine import InferenceSession, SessionConfig
from repro.kernels import ops
from repro.kernels.conv2d import conv2d_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.maxpool2d import maxpool2d_pallas

NETS = {**PAPER_CNNS, **EXTRA_CNNS}
BATCHES = (1, 256)
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but not read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _pallas_session(name):
    """The ``"pallas"`` session of ``name``; its graph is what it deploys."""
    return InferenceSession(NETS[name](0),
                            config=SessionConfig(backend="pallas"))


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("name", list(NETS))
def test_cnn_kernels_compile(name, one_chip):
    graph = _pallas_session(name).graph
    smap = graph.shape_map()
    plan = jax_exec.pallas_layer_plan(graph)
    layers = [l for l in graph.layers if plan.get(l.name) == "pallas"]
    assert layers
    for layer in layers:
        in_shape = tuple(smap[layer.inputs[0]])
        for n in BATCHES:
            x = jax.ShapeDtypeStruct((n,) + in_shape, jnp.float32,
                                     sharding=one_chip)
            if isinstance(layer, Conv2D):
                w, b = (jax.ShapeDtypeStruct(a.shape, jnp.float32,
                                             sharding=one_chip)
                        for a in (layer.weights, layer.bias))
                fn = functools.partial(
                    conv2d_pallas, strides=layer.strides,
                    padding=layer.padding, act=layer.activation
                    if layer.activation != "softmax" else None,
                    alpha=layer.alpha, interpret=False)
                text = _compile_text(fn, x, w, b)
            else:
                fn = functools.partial(maxpool2d_pallas, size=layer.size,
                                       strides=layer.strides,
                                       interpret=False)
                text = _compile_text(fn, x)
            assert CUSTOM_CALL in text, (name, layer.name, n)


@pytest.mark.parametrize("name", list(NETS))
def test_pallas_program_has_one_kernel_per_layer(name, one_chip,
                                                 monkeypatch):
    # the session's own jitted program, as it compiles on a TPU host
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    sess = _pallas_session(name)
    want = sum(v == "pallas"
               for v in jax_exec.pallas_layer_plan(sess.graph).values())
    x = jax.ShapeDtypeStruct((256,) + tuple(sess.input_shape), jnp.float32,
                             sharding=one_chip)
    text = sess.backend._fn.lower(x).compile().as_text()
    assert text.count(CUSTOM_CALL) == want


def test_flash_attention_compiles(one_chip):
    # gemma3-4b's published attention: 8 query / 4 kv heads of 256
    q = jax.ShapeDtypeStruct((1, 8, 1024, 256), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 1024, 256), jnp.bfloat16,
                              sharding=one_chip)
    fn = functools.partial(flash_attention_pallas, causal=True,
                           block_q=512, block_k=512, interpret=False)
    assert CUSTOM_CALL in _compile_text(fn, q, kv, kv)
