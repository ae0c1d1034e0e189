"""The batched program's output layout as the TPU's own compiler chooses
it, for a TPU v5e that is described, not attached: a compile, not a run.

Left to the compiler, a flat ``uint8`` output is laid out by its shape
(``u8[8, 90, 360]`` puts the batch axis between the rows and the row's
bytes), so its read-back would be strided; ``flat_output_format`` pins it
row-major. Where a row holds whole ``uint32`` words the output is packed
into them, and the words are formed with no array whose minor axis, as the
TPU lays it out, holds a word's samples (such an array is tiled 32-fold
larger). The topology is described inside a fixture, never while a module
is imported: only one process at a time may load the TPU's library.
"""

import os

import jax
import numpy as np
import pytest

from flyimg_tpu.ops.compose import WORD_BYTES, flat_output_format, flatten_images


@pytest.fixture(scope="module")
def v5e_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(chip, word_size=None, dtype=np.uint8, **jit_kwargs):
    spec = jax.ShapeDtypeStruct((8, 90, 120, 3), dtype, sharding=chip)
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(lambda x: flatten_images(x + 1, word_size),
                       **jit_kwargs).lower(spec).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)


def _output_order(chip, **jit_kwargs):
    return _compile(chip, **jit_kwargs).output_formats.layout.major_to_minor


def test_the_flat_output_is_pinned_row_major_for_the_tpu(v5e_chip):
    assert _output_order(v5e_chip) == (1, 0, 2)
    assert _output_order(
        v5e_chip, out_shardings=flat_output_format(v5e_chip)) == (0, 1, 2)


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
def test_the_words_are_packed_from_planes_for_the_tpu(v5e_chip, dtype):
    from test_packed_readback import minor_dims

    compiled = _compile(v5e_chip, WORD_BYTES, dtype,
                        out_shardings=flat_output_format(v5e_chip))
    per_word = WORD_BYTES // np.dtype(dtype).itemsize
    assert compiled.output_formats.layout.major_to_minor == (0, 1, 2)
    text = compiled.as_text()
    assert f"u32[8,90,{360 // per_word}]" in text
    sizes = minor_dims(text)
    assert sizes and not sizes & {2, 4}, sizes
