"""Device ops: the pixel kernels that replace ImageMagick's C internals.

Everything here is jit-able, batchable (vmap-friendly), static-shape JAX.
The reference runs these as per-image native processes (convert/mogrify,
reference src/Core/Processor/Processor.php:15-33); here they are XLA programs
whose hot paths (resampling) are expressed as einsums so they land on the MXU.
"""

from flyimg_tpu.ops.resample import resample_image, resample_matrix  # noqa: F401
from flyimg_tpu.ops.filters import gaussian_blur, sharpen, unsharp_mask  # noqa: F401
from flyimg_tpu.ops.color import to_grayscale, monochrome_dither  # noqa: F401
from flyimg_tpu.ops.rotate import rotate_image  # noqa: F401
from flyimg_tpu.ops.pad import extent_pad  # noqa: F401
from flyimg_tpu.ops.pixelate import pixelate_image, pixelate_images  # noqa: F401
from flyimg_tpu.ops.compose import build_program, run_plan  # noqa: F401
