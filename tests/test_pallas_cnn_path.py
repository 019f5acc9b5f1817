"""The Pallas-kernel CNN inference path equals the XLA oracle and the
generated C — all three deployment artifacts of the same trained model."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cnn_paper import PAPER_CNNS
from repro.core import jax_exec, passes


@pytest.mark.parametrize("name", list(PAPER_CNNS))
def test_pallas_path_matches_oracle(name):
    g = passes.optimize(PAPER_CNNS[name](), simd_multiple=4)
    x = np.random.default_rng(5).normal(size=(2,) + g.input_shape
                                        ).astype(np.float32)
    ref = np.asarray(jax_exec.forward(g, jnp.asarray(x)))
    got = np.asarray(jax_exec.forward_pallas(g, jnp.asarray(x)))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_pallas_describe_reports_device_and_layers():
    from repro.configs.cnn_paper import residual_cnn
    from repro.engine import InferenceSession, SessionConfig
    sess = InferenceSession(residual_cnn(),
                            config=SessionConfig(backend="pallas"))
    d = sess.backend.describe()
    assert (d["platform"], d["interpret"]) == ("cpu", True)
    assert d["device_kind"]
    # convs run as kernels; the depthwise conv, Add, Concat and pools
    # fall back to jnp ops
    assert d["layers"]["stem"] == d["layers"]["head"] == "pallas"
    assert d["layers"]["dw"] == d["layers"]["mix"] == "jnp"
    assert set(d["layers"]) == {l.name for l in sess.graph.layers[1:]}
