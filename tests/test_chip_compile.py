"""Compiles at real sizes for a described TPU v5e (no chip needed).

The TPU compiler is installed wherever libtpu is, and it compiles for a
chip that is described and not attached. That catches, at no chip time,
what interpret mode cannot: Mosaic lowering gaps, VMEM over-use, and
programs that do not fit the device's HBM. Nothing runs, so results and
times are not checked here (``chip_smoke.py`` does that on a chip).

This is the only test file that describes the chip. The topology is
described inside a module-scoped fixture, never at import: one process at
a time may load libtpu, and test workers import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.cases import kernel_cases
from repro.launch import steps as steps_lib
from repro.models import model_zoo

pytestmark = pytest.mark.kernels

HBM_BYTES = 16 * 2 ** 30          # one v5e chip

# the olmo_1b request chip_smoke.py serves: 4 x (128-token prompt + 32
# new tokens), cache sized as the Engine sizes it
BATCH, PROMPT, NEW = 4, 128, 32
MAX_SEQ = PROMPT + NEW + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    # libtpu otherwise writes its compiler logs under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("case", kernel_cases(), ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(case, one_chip):
    compiled = jax.jit(case.op).lower(*_on(one_chip, case.args)).compile()
    # the Pallas kernel itself, not a fallback, is in the program
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def olmo_1b():
    cfg = get_config("olmo_1b")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (16, 2048, 50304)
    return cfg


def _fits_hbm(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, (mem.argument_size_in_bytes,
                              mem.temp_size_in_bytes)


def test_olmo_1b_prefill_compiles_and_fits(olmo_1b, one_chip):
    params = _on(one_chip, model_zoo.param_shapes(olmo_1b))
    batch = _on(one_chip, {"tokens": jax.ShapeDtypeStruct(
        (BATCH, PROMPT), jnp.int32)})
    step = jax.jit(steps_lib.make_prefill_step(olmo_1b, MAX_SEQ))
    _fits_hbm(step.lower(params, batch).compile())


def test_olmo_1b_decode_compiles_and_fits(olmo_1b, one_chip):
    params = _on(one_chip, model_zoo.param_shapes(olmo_1b))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model_zoo.init_cache(olmo_1b, BATCH, MAX_SEQ)))
    tokens = _on(one_chip, jax.ShapeDtypeStruct((BATCH,), jnp.int32))
    step = jax.jit(steps_lib.make_decode_step(olmo_1b))
    _fits_hbm(step.lower(params, cache, tokens).compile())
