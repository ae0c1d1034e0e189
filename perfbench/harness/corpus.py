"""The corpus: encoded originals made from the seed. What a frame shows is
its kind's (``corpora/<kind>.py``: ``make_image(seed, index, width, height)``
-> ``[height, width, 3]`` uint8); the encoding is the configuration's frame.

Made with numpy and Pillow only; nothing of the program is used here.
"""

from __future__ import annotations

import io
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List

import numpy as np
from PIL import Image

SUBSAMPLING = {"4:4:4": 0, "4:2:2": 1, "4:2:0": 2}


def encode_jpeg(rgb: np.ndarray, quality: int, subsampling: str) -> bytes:
    buf = io.BytesIO()
    Image.fromarray(rgb).save(buf, format="JPEG", quality=int(quality),
                              subsampling=SUBSAMPLING[subsampling])
    return buf.getvalue()


def make_corpus(make_image: Callable[[int, int, int, int], np.ndarray], seed: int,
                frame: Dict[str, Any], count: int, threads: int = 8) -> List[bytes]:
    """``count`` distinct encoded originals of the configuration's frame."""
    def one(index: int) -> bytes:
        rgb = make_image(seed, index, frame["width"], frame["height"])
        return encode_jpeg(rgb, frame["quality"], frame["subsampling"])

    with ThreadPoolExecutor(max_workers=threads) as pool:
        return list(pool.map(one, range(count)))
