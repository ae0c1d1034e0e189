"""Face backend registry: one detect/blur/crop contract, three engines.

The reference has exactly one face engine — a shell-out to `facedetect`
(OpenCV Haar cascades; FaceDetectProcessor.php:27-29). This framework
keeps the same list-of-boxes contract behind a pluggable backend chosen
by the ``face_backend`` / ``face_checkpoint`` app parameters:

- ``haar``   — the reference's detector family, evaluated in-process from
  the same cascade XML files (models/haar.py). Real face detection with
  zero learned state of our own; the parity default where cascades exist.
- ``blazeface`` — the TPU-native north star (models/blazeface.py): a
  BlazeFace convnet served batched through the runtime; needs a trained
  checkpoint (one is packaged; ``face_checkpoint`` overrides).
- ``facefind`` — the dependency-free classical skin-blob proposer
  (models/facefind.py); the fallback when neither is available.

Blur (pixelation) and crop are shared regardless of the detector:
``blur_faces`` is one image through the batched ``uint8`` program of
ops/pixelate.py (the handler sends its images there through the device
controller), ``crop_face`` a host slice.
"""

from __future__ import annotations

import os
from typing import List, Optional, Tuple

import numpy as np

from flyimg_tpu.models import facefind

Box = Tuple[int, int, int, int]

PACKAGED_BLAZEFACE = os.path.join(
    os.path.dirname(__file__), "weights", "blazeface"
)


class HaarBackend:
    """In-process Haar cascade detection (reference parity backend)."""

    def __init__(
        self,
        cascade_path: Optional[str] = None,
        *,
        min_neighbors: int = 2,
    ) -> None:
        from flyimg_tpu.models import haar

        self._haar = haar
        self.cascade_path = cascade_path or haar.find_cascade()
        if self.cascade_path is None:
            raise RuntimeError("no haar cascade XML available")
        self.min_neighbors = min_neighbors

    def detect_faces(self, rgb: np.ndarray) -> List[Box]:
        return self._haar.detect_faces(
            rgb,
            cascade_path=self.cascade_path,
            min_neighbors=self.min_neighbors,
        )

    blur_faces = staticmethod(facefind.blur_faces)
    crop_face = staticmethod(facefind.crop_face)


class BlazeFaceBackend:
    """BlazeFace convnet detection; fixed 128x128 input makes batched
    serving trivial (one jitted program, period).

    Serving role (round-5 decision, benchmarks/blazeface_eval_r5.json —
    300 held-out composite scenes vs the Haar oracle): at the 0.8
    operating point BlazeFace recovers 98% of Haar's boxes at mean IoU
    0.86 but still proposes ~0.19 extra boxes per Haar box (P 0.82, and
    some of those are pasted faces Haar itself missed). That asymmetry
    sets the default: ``auto`` keeps Haar first — fb_1 pixelating a
    non-face is the costly error — and BlazeFace is the explicit choice
    when batched-throughput wins: it is the ONE detector whose work is a
    single fixed-shape jitted program, so concurrent face requests ride
    the device batcher instead of per-image host Haar scans.

    Why not a higher threshold: on composites, precision keeps rising to
    0.94 at score 0.95 (blazeface_eval_hi_r5.json) — but the REAL-photo
    fixtures break there (portrait 0/1, group photo 2/4; the composite
    score distribution does not transfer), so 0.8 is the highest point
    that holds the fixture gates (tests/test_faces.py) and stays."""

    def __init__(self, checkpoint: str, *, score_threshold: float = 0.8) -> None:
        from flyimg_tpu.models import blazeface

        self._bf = blazeface
        self.params = blazeface.load_checkpoint(checkpoint)
        self.score_threshold = score_threshold

    def detect_faces(self, rgb: np.ndarray) -> List[Box]:
        return self._bf.detect_faces(
            self.params, rgb, score_threshold=self.score_threshold
        )

    # batched serving path (handler submits via the aux batcher): the
    # payload is the request's NETWORK INPUTS, made by the request's own
    # thread (six Pillow resizes a large frame); the runner, on the device
    # controller's one executor thread, stacks them, runs the forward in
    # chunks, and maps the boxes back
    def prepare_face_work(self, rgb: np.ndarray, threshold: float = 0.0):
        del threshold
        return self._bf.prepare_views(rgb)

    def detect_faces_batched(self, items, stats=None) -> List[List[Box]]:
        """The aux runner. ``stats`` (a dict) gains the launch's ``views``,
        ``slots`` and ``forwards``, and the seconds of its parts
        (blazeface.detect_prepared)."""
        return self._bf.detect_prepared(
            self.params, items,
            score_threshold=self.score_threshold, stats=stats,
        )

    blur_faces = staticmethod(facefind.blur_faces)
    crop_face = staticmethod(facefind.crop_face)


class FacefindBackend:
    """Classical skin-blob proposer (no external data requirements).

    Opt-in ONLY (``face_backend: facefind``): it proposes skin-toned
    REGIONS, not faces, so fb_1 under it can pixelate arms/crowds. That
    trade-off must be chosen by an operator, never reached by fallback."""

    detect_faces = staticmethod(facefind.detect_faces)
    prepare_face_work = staticmethod(facefind.prepare_face_work)
    detect_faces_batched = staticmethod(facefind.detect_faces_batched)
    blur_faces = staticmethod(facefind.blur_faces)
    crop_face = staticmethod(facefind.crop_face)


class NullBackend:
    """Zero-faces backend: face options silently no-op, exactly the
    reference's behavior when its facedetect binary is missing
    (FaceDetectProcessor.php:24,53 — `if (!file_exists(...)) return;`).
    A wrong transform (pixelating skin that isn't a face) is worse than
    none, so this — not the skin proposer — is the fallback when no real
    detector is installed."""

    @staticmethod
    def detect_faces(rgb: np.ndarray) -> List[Box]:
        del rgb
        return []

    # zero boxes no-op both downstream ops, matching the reference's
    # "no facedetect binary -> the option does nothing" contract
    blur_faces = staticmethod(facefind.blur_faces)
    crop_face = staticmethod(facefind.crop_face)


def make_face_backend(
    name: str = "auto", checkpoint: Optional[str] = None
):
    """Resolve the serving face backend. ``auto`` prefers the reference's
    own detector family (haar) where cascade files exist, then the
    packaged BlazeFace checkpoint, then the zero-faces no-op backend
    (reference semantics when no detector is installed); the skin-blob
    proposer is never reached implicitly. ``blazeface`` uses
    ``checkpoint`` or the packaged weights."""
    name = (name or "auto").lower()
    if name == "blazeface":
        ckpt = checkpoint or PACKAGED_BLAZEFACE
        if not os.path.exists(ckpt):
            raise RuntimeError(
                f"blazeface checkpoint not found at {ckpt}; set "
                "face_checkpoint or train one with tools/train_blazeface.py"
            )
        return BlazeFaceBackend(ckpt)
    if name == "haar":
        return HaarBackend(checkpoint)
    if name == "facefind":
        return FacefindBackend()
    if name in ("none", "null"):
        return NullBackend()
    if name == "auto":
        from flyimg_tpu.models import haar

        if haar.available():
            return HaarBackend()
        if os.path.exists(PACKAGED_BLAZEFACE):
            return BlazeFaceBackend(PACKAGED_BLAZEFACE)
        return NullBackend()
    raise ValueError(f"unknown face_backend {name!r}")
