"""Host codec layer: round-trips, native/python probe agreement, alpha,
DCT prescale, and the parallel decode pool.

The reference's codec behavior lives in external binaries (ImageMagick
decode, cjpeg, cwebp — reference src/Core/Processor/Processor.php:15-33);
here it is the in-process fastcodec library + PIL fallback, so this suite is
the conformance net for that replacement.
"""

import io

import numpy as np
import pytest
from PIL import Image

from flyimg_tpu.codecs import decode, encode, sniff
from flyimg_tpu.codecs import native_codec


def _img(h=40, w=56, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 255, (h, w, 3), dtype=np.uint8)


# ---- encode/decode round trips --------------------------------------------

@pytest.mark.parametrize("fmt,mime", [
    ("png", "image/png"),
    ("jpg", "image/jpeg"),
    ("webp", "image/webp"),
    ("gif", "image/gif"),
])
def test_round_trip_formats(fmt, mime):
    img = _img()
    blob = encode(img, fmt, quality=95)
    assert sniff(blob).mime == mime
    out = decode(blob)
    assert out.rgb.shape == img.shape
    if fmt == "png":  # lossless: exact
        np.testing.assert_array_equal(out.rgb, img)


def test_png_alpha_round_trip():
    img = _img(seed=1)
    alpha = np.linspace(0, 255, 40 * 56, dtype=np.uint8).reshape(40, 56)
    blob = encode(img, "png", alpha=alpha)
    out = decode(blob)
    assert out.alpha is not None
    np.testing.assert_array_equal(out.rgb, img)
    np.testing.assert_array_equal(out.alpha, alpha)


def test_jpeg_quality_orders_size():
    img = _img(seed=2)
    small = encode(img, "jpg", quality=30)
    large = encode(img, "jpg", quality=95)
    assert len(small) < len(large)


def test_webp_lossless_flag():
    img = _img(seed=3)
    blob = encode(img, "webp", webp_lossless=True)
    out = decode(blob)
    np.testing.assert_array_equal(out.rgb, img)


# ---- native probe vs python sniffer ---------------------------------------

def _fixture_blobs():
    img = _img(seed=4)
    blobs = {
        "image/png": encode(img, "png"),
        "image/jpeg": encode(img, "jpg"),
        "image/webp": encode(img, "webp"),
        "image/gif": encode(img, "gif"),
    }
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, "BMP")
    blobs["image/bmp"] = buf.getvalue()
    blobs["application/pdf"] = b"%PDF-1.4\n" + b"x" * 64
    return blobs


@pytest.mark.skipif(
    not native_codec.available(), reason="native codec not built"
)
def test_native_probe_agrees_with_python_sniff():
    for mime, blob in _fixture_blobs().items():
        head = blob[:65536]
        info = sniff(head)
        probed = native_codec.probe(head)
        assert probed is not None
        p_mime, p_w, p_h, p_depth = probed
        assert p_mime == info.mime == mime
        if info.width is not None:
            assert (p_w, p_h) == (info.width, info.height), mime
        if mime in ("image/png", "image/jpeg", "image/webp"):
            assert p_depth == 8


@pytest.mark.skipif(
    not native_codec.available(), reason="native codec not built"
)
def test_native_probe_garbage_and_truncated():
    assert native_codec.probe(b"")[0] == "application/octet-stream"
    assert native_codec.probe(b"\x00" * 64)[0] == "application/octet-stream"
    png_head = encode(_img(), "png")[:13]  # magic only, no IHDR dims
    mime, w, h, _ = native_codec.probe(png_head)
    assert mime == "image/png"
    assert (w, h) == (0, 0)


def test_jpeg_fill_bytes_before_marker():
    """0xFF fill bytes before a marker are legal JPEG; both probers must
    still find the SOF dims."""
    blob = encode(_img(), "jpg")
    sof = max(blob.find(b"\xff\xc0"), blob.find(b"\xff\xc2"))
    assert sof > 0
    padded = blob[:sof] + b"\xff" + blob[sof:]  # one fill byte before SOF0
    info = sniff(padded)
    assert (info.width, info.height) == (56, 40)
    if native_codec.available():
        mime, w, h, depth = native_codec.probe(padded)
        assert (mime, w, h, depth) == ("image/jpeg", 56, 40, 8)


# ---- native PNG specifics --------------------------------------------------

@pytest.mark.skipif(
    not native_codec.available(), reason="native codec not built"
)
def test_native_png_matches_pil():
    img = _img(seed=5)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, "PNG")
    decoded = native_codec.png_decode(buf.getvalue())
    assert decoded is not None
    pixels, channels = decoded
    assert channels == 3
    np.testing.assert_array_equal(pixels, img)


@pytest.mark.skipif(
    not native_codec.available(), reason="native codec not built"
)
def test_native_png_palette_transparency():
    """Palette PNGs with tRNS must surface alpha (the simplified libpng API
    expands palette + transparency)."""
    img = Image.fromarray(_img(seed=6)).convert(
        "P", palette=Image.Palette.ADAPTIVE
    )
    img.info["transparency"] = 0
    buf = io.BytesIO()
    img.save(buf, "PNG", transparency=0)
    decoded = native_codec.png_decode(buf.getvalue())
    assert decoded is not None
    _, channels = decoded
    assert channels == 4


# ---- DCT prescale hint -----------------------------------------------------

def test_jpeg_decode_prescale_hint():
    """A small target hint lets the decoder return a DCT-downscaled image
    (>= 2x the target box), not the full resolution."""
    img = _img(h=640, w=896, seed=7)
    blob = encode(img, "jpg", quality=90)
    full = decode(blob)
    assert full.rgb.shape[:2] == (640, 896)
    hinted = decode(blob, target_hint=(100, 100))
    assert hinted.rgb.shape[0] < 640
    assert hinted.rgb.shape[0] >= 200  # still >= 2x the 100px target


# ---- decode pool -----------------------------------------------------------

@pytest.mark.skipif(
    not native_codec.available(), reason="native codec not built"
)
def test_decode_pool_batch():
    blobs = [encode(_img(seed=s), "jpg", quality=92) for s in range(6)]
    blobs.append(b"not a jpeg")
    pool = native_codec.DecodePool(n_threads=2)
    try:
        outs = pool.decode_batch(blobs)
        assert len(outs) == 7
        for out in outs[:6]:
            assert out is not None and out.shape == (40, 56, 3)
        assert outs[6] is None
    finally:
        pool.close()


def test_trellis_encode_smaller_at_equal_quality():
    """The moz_1 trellis encoder must beat the plain optimized encoder on
    bytes at ~equal PSNR (the whole point of trellis quantization), and
    its output must be decodable everywhere."""
    from flyimg_tpu.codecs import native_codec

    if not native_codec.available():
        pytest.skip("fastcodec not built")
    # continuous-tone content: smooth gradients + texture, not flat noise
    yy, xx = np.mgrid[0:320, 0:480]
    rng = np.random.default_rng(3)
    img = np.stack(
        [
            120 + 90 * np.sin(xx / 37.0) + 30 * np.cos(yy / 23.0),
            100 + 80 * np.cos((xx + yy) / 53.0),
            90 + 70 * np.sin(yy / 31.0 + xx / 91.0),
        ],
        axis=-1,
    )
    img = np.clip(img + rng.normal(0, 6, img.shape), 0, 255).astype(np.uint8)

    def psnr(a, b):
        mse = np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2)
        return 10 * np.log10(255.0**2 / mse)

    for q in (75, 85):
        base = native_codec.jpeg_encode(img, q, optimize=True, progressive=True)
        tre = native_codec.jpeg_encode_trellis(img, q)
        assert base is not None and tre is not None
        d_base = np.asarray(Image.open(io.BytesIO(base)).convert("RGB"))
        d_tre = np.asarray(Image.open(io.BytesIO(tre)).convert("RGB"))
        assert d_tre.shape == img.shape
        # smaller bytes...
        assert len(tre) < len(base), (q, len(tre), len(base))
        # ...at comparable quality (within half a dB)
        assert psnr(img, d_tre) > psnr(img, d_base) - 0.5


def test_trellis_encode_subsampling_dims():
    from flyimg_tpu.codecs import native_codec

    if not native_codec.available():
        pytest.skip("fastcodec not built")
    # smooth photographic-like content (gradients): chroma subsampling
    # should cost little PSNR here, so a low score flags a plane-geometry
    # bug (garbled chroma) rather than ordinary subsampling loss. Odd dims
    # exercise the chroma padding/rounding paths; the sampling set covers
    # the IM -sampling-factor geometries the reference forwards
    # (1x1=4:4:4, 2x2=4:2:0, 2x1=4:2:2, 1x2=4:4:0, 4x1=4:1:1)
    yy, xx = np.mgrid[0:123, 0:157]
    img = np.stack(
        [
            (xx * 255 / 156),
            (yy * 255 / 122),
            ((xx + yy) * 255 / 278),
        ],
        axis=-1,
    ).astype(np.uint8)
    for sampling in ((1, 1), (2, 2), (2, 1), (1, 2), (4, 1)):
        blob = native_codec.jpeg_encode_trellis(img, 85, sampling=sampling)
        assert blob is not None, sampling
        out = Image.open(io.BytesIO(blob))
        assert out.size == (157, 123), sampling
        dec = np.asarray(out.convert("RGB")).astype(np.float64)
        mse = np.mean((dec - img.astype(np.float64)) ** 2)
        assert 10 * np.log10(255.0**2 / mse) > 30.0, sampling
    # invalid factor pairs are rejected, not silently coerced
    assert native_codec.jpeg_encode_trellis(img, 85, sampling=(3, 3)) is None
    assert native_codec.jpeg_encode(img, 85, sampling=(5, 1)) is None


def test_moz_flag_switches_encoder(tmp_path):
    """moz_0 must produce a different (baseline) encode than the default
    trellis path through the full handler."""
    from flyimg_tpu.codecs import native_codec

    if not native_codec.available():
        pytest.skip("fastcodec not built")
    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.service.handler import ImageHandler
    from flyimg_tpu.storage import make_storage

    params = AppParameters(
        {"upload_dir": str(tmp_path / "u"), "tmp_dir": str(tmp_path / "t")}
    )
    handler = ImageHandler(make_storage(params), params)
    rng = np.random.default_rng(5)
    arr = np.clip(
        rng.normal(128, 40, (200, 300, 3)), 0, 255
    ).astype(np.uint8)
    src = str(tmp_path / "m.png")
    Image.fromarray(arr).save(src)
    moz = handler.process_image("w_150,o_jpg", src)
    plain = handler.process_image("w_150,o_jpg,moz_0", src)
    assert moz.content != plain.content
    for blob in (moz.content, plain.content):
        assert Image.open(io.BytesIO(blob)).size == (150, 100)


def test_webp_alpha_round_trip_native():
    """Transparent WebP must keep its alpha through the native codec in
    BOTH directions (cwebp/dwebp parity; the RGB-only path would
    silently flatten)."""
    from flyimg_tpu.codecs import native_codec

    if not native_codec.available():
        pytest.skip("fastcodec not built")
    img = _img(seed=6)
    alpha = np.linspace(10, 245, 40 * 56, dtype=np.uint8).reshape(40, 56)
    blob = encode(img, "webp", alpha=alpha, webp_lossless=True)
    out = decode(blob)
    assert out.mime == "image/webp"
    assert out.alpha is not None
    np.testing.assert_array_equal(out.alpha, alpha)
    np.testing.assert_array_equal(out.rgb, img)


def test_webp_opaque_still_rgb():
    from flyimg_tpu.codecs import native_codec

    if not native_codec.available():
        pytest.skip("fastcodec not built")
    img = _img(seed=7)
    blob = encode(img, "webp", webp_lossless=True)
    out = decode(blob)
    assert out.alpha is None
    np.testing.assert_array_equal(out.rgb, img)


def test_exif_orientation_matches_pil_all_eight():
    """The reference always emits -auto-orient (ImageProcessor.php:78); the
    native JPEG path applies EXIF orientation itself (codecs/exif.py). Pin
    every orientation 1..8 bit-exactly against PIL's exif_transpose — the
    same transform ImageMagick's auto-orient performs."""
    import io

    from PIL import Image, ImageOps

    rng = np.random.default_rng(5)
    arr = rng.integers(0, 255, (40, 60, 3), dtype=np.uint8)
    for orient in range(1, 9):
        img = Image.fromarray(arr)
        exif = img.getexif()
        exif[0x0112] = orient
        buf = io.BytesIO()
        img.save(buf, "JPEG", quality=98, exif=exif)
        data = buf.getvalue()
        ours = decode(data).rgb
        ref = np.asarray(
            ImageOps.exif_transpose(Image.open(io.BytesIO(data))).convert("RGB")
        )
        assert ours.shape == ref.shape, orient
        np.testing.assert_array_equal(ours, ref, err_msg=f"orientation {orient}")


def test_exif_malformed_offsets_never_raise_or_corrupt():
    """EXIF IFD offsets are attacker-controlled. Two crafted cases:
    (a) the 0x0112 tag id is readable but its value field lies past EOF —
    orientation must fall back to 1, not raise struct.error (which would
    turn every request on that image into a 500), and the st_0 graft must
    skip a segment whose declared length runs past EOF (a short copy
    would desync declared vs actual bytes — a corrupt output JPEG);
    (b) the IFD offset points PAST the APP1 segment into trailing file
    bytes — the out-of-segment entry must not be trusted, and any grafted
    segment's declared length must equal its actual bytes."""
    import struct as _s

    from flyimg_tpu.codecs.exif import jpeg_orientation
    from flyimg_tpu.codecs.metadata import collect_jpeg, inject_jpeg

    def app1(payload: bytes, declared_len: int) -> bytes:
        return b"\xff\xe1" + _s.pack(">H", declared_len) + payload

    # (a) truncated: full entry would be 12 bytes; keep only tag+type
    tiff = b"II*\x00" + _s.pack("<I", 8) + _s.pack("<H", 1)
    entry_head = _s.pack("<HH", 0x0112, 3)  # tag readable, value absent
    payload = b"Exif\x00\x00" + tiff + entry_head
    declared = 2 + len(payload) + 8  # claims the full entry is present
    truncated = b"\xff\xd8" + app1(payload, declared)
    assert jpeg_orientation(truncated) == 1
    assert collect_jpeg(truncated).exif_tiff is None

    # (b) IFD offset escapes the segment: entry lives in trailing bytes
    tiff_esc = b"II*\x00" + _s.pack("<I", 64)  # IFD far past the segment
    payload_esc = b"Exif\x00\x00" + tiff_esc
    seg = app1(payload_esc, 2 + len(payload_esc))
    trailer = b"\x00" * 50 + _s.pack("<H", 1) + _s.pack(
        "<HHIHH", 0x0112, 3, 1, 6, 0
    )
    crafted = b"\xff\xd8" + seg + trailer + b"\xff\xd9"
    # the out-of-segment entry must not be trusted for rotation...
    assert jpeg_orientation(crafted) == 1
    # ...and any grafted APP1 must declare exactly the bytes it carries
    meta = collect_jpeg(crafted)
    base = encode(_img(seed=9), "jpg")
    grafted = inject_jpeg(base, meta)
    pos = 2
    while pos + 4 <= len(grafted) and grafted[pos] == 0xFF:
        marker = grafted[pos + 1]
        if marker in (0xD8,):
            pos += 2
            continue
        if marker in (0xDA, 0xD9):
            break
        seglen = _s.unpack(">H", grafted[pos + 2 : pos + 4])[0]
        assert pos + 2 + seglen <= len(grafted)
        pos += 2 + seglen


def test_parse_sampling_factor_grammar():
    """IM -sampling-factor grammar: geometry HxV and ratio forms map to
    luma factor pairs; garbage raises instead of silently coercing
    (reference forwards the raw value to convert, which errors —
    ImageProcessor.php:105)."""
    import pytest as _pytest

    from flyimg_tpu.codecs import parse_sampling_factor
    from flyimg_tpu.exceptions import InvalidArgumentException

    assert parse_sampling_factor("1x1") == (1, 1)
    assert parse_sampling_factor("2x2") == (2, 2)
    assert parse_sampling_factor("2x1") == (2, 1)
    assert parse_sampling_factor("1x2") == (1, 2)
    assert parse_sampling_factor("4:4:4") == (1, 1)
    assert parse_sampling_factor("4:2:0") == (2, 2)
    assert parse_sampling_factor("4:2:2") == (2, 1)
    assert parse_sampling_factor("4:1:1") == (4, 1)
    assert parse_sampling_factor(None) == (1, 1)
    assert parse_sampling_factor("") == (1, 1)
    for bad in ("abc", "0x1", "5x1", "3x3", "4x4", "4:3:2"):
        with _pytest.raises(InvalidArgumentException):
            parse_sampling_factor(bad)


def test_pool_encode_batch_matches_single_encode():
    """The pooled batch encode must produce byte-identical output to the
    single-image entry points for both the trellis and plain paths."""
    from flyimg_tpu.codecs import native_codec

    if not native_codec.available():
        pytest.skip("fastcodec not built")
    rng = np.random.default_rng(11)
    frames = [
        np.clip(rng.normal(120, 40, (90 + 8 * i, 130, 3)), 0, 255).astype(np.uint8)
        for i in range(5)
    ]
    pool = native_codec.DecodePool(2)
    try:
        for trellis in (True, False):
            batched = pool.encode_batch(
                frames, 85, trellis=trellis, sampling=(2, 2)
            )
            for frame, blob in zip(frames, batched):
                if trellis:
                    single = native_codec.jpeg_encode_trellis(
                        frame, 85, sampling=(2, 2)
                    )
                else:
                    single = native_codec.jpeg_encode(
                        frame, 85, optimize=True, progressive=True,
                        sampling=(2, 2),
                    )
                assert blob == single
    finally:
        pool.close()


def _icc_profile_bytes():
    """A real (tiny) ICC profile: PIL ships sRGB via ImageCms."""
    from PIL import ImageCms

    return ImageCms.ImageCmsProfile(ImageCms.createProfile("sRGB")).tobytes()


def test_st0_metadata_carry_jpeg_and_png(tmp_path):
    """st_0 (default) preserves EXIF + ICC + XMP like the reference's
    no-strip convert (ImageProcessor.php:97-99), across jpeg->jpeg,
    jpeg->png, png->jpeg, png->png; the default (strip: 1, reference
    parameters.yml:97) drops everything."""
    from PIL import Image as PILImage

    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.service.handler import ImageHandler
    from flyimg_tpu.storage import make_storage

    params = AppParameters(
        {"upload_dir": str(tmp_path / "u"), "tmp_dir": str(tmp_path / "t")}
    )
    handler = ImageHandler(make_storage(params), params)

    icc = _icc_profile_bytes()
    rng = np.random.default_rng(21)
    arr = rng.integers(0, 255, (120, 160, 3), dtype=np.uint8)

    img = PILImage.fromarray(arr)
    exif = img.getexif()
    exif[0x0112] = 6          # orientation: baked into pixels, tag reset
    exif[0x010F] = "acme-cam"  # Make: must survive verbatim
    jpg_src = str(tmp_path / "src.jpg")
    img.save(jpg_src, "JPEG", quality=92, exif=exif, icc_profile=icc)
    png_src = str(tmp_path / "src.png")
    img.save(png_src, "PNG", exif=exif, icc_profile=icc)

    for src, out_fmt in [
        (jpg_src, "jpg"), (jpg_src, "png"), (png_src, "jpg"), (png_src, "png"),
    ]:
        result = handler.process_image(f"w_100,o_{out_fmt},st_0", src)
        out = PILImage.open(io.BytesIO(result.content))
        out.load()
        assert out.info.get("icc_profile") == icc, (src, out_fmt)
        carried = out.getexif()
        assert carried[0x010F] == "acme-cam", (src, out_fmt)
        # orientation was applied to pixels (jpeg decode path), so the
        # carried tag must not instruct viewers to rotate again
        assert carried.get(0x0112, 1) == 1, (src, out_fmt)

    stripped = handler.process_image("w_100,o_jpg", jpg_src)
    sout = PILImage.open(io.BytesIO(stripped.content))
    sout.load()
    assert "icc_profile" not in sout.info
    assert 0x010F not in sout.getexif()


def test_st0_multisegment_icc_round_trip(tmp_path):
    """ICC profiles larger than one APP2 segment (65519 bytes) must
    re-assemble on collect and re-split on inject byte-identically."""
    from flyimg_tpu.codecs import metadata as meta_mod

    icc = bytes(range(256)) * 600  # ~150 KB -> 3 APP2 chunks
    meta = meta_mod.SourceMetadata(icc=icc)
    base = encode(_img(seed=8), "jpg", quality=90)
    grafted = meta_mod.inject_jpeg(base, meta)
    back = meta_mod.collect_jpeg(grafted)
    assert back.icc == icc
    # and PIL agrees the train parses as one profile
    from PIL import Image as PILImage

    out = PILImage.open(io.BytesIO(grafted))
    out.load()
    assert out.info.get("icc_profile") == icc


def test_png_exif_orientation_native_and_pil_paths_agree(monkeypatch):
    """PNG eXIf orientation must be applied exactly ONCE on both decode
    paths: the native path applies it explicitly (_orient_png), the PIL
    fallback already runs ImageOps.exif_transpose — double-applying
    yielded a 180-degree-rotated image."""
    from PIL import Image as PILImage

    from flyimg_tpu.codecs import native_codec

    arr = _img(h=40, w=60, seed=13)
    img = PILImage.fromarray(arr)
    exif = img.getexif()
    exif[0x0112] = 6  # 90-degree rotation -> dims swap
    buf = io.BytesIO()
    img.save(buf, "PNG", exif=exif)
    data = buf.getvalue()

    native = decode(data)
    assert native.rgb.shape[:2] == (60, 40)

    monkeypatch.setattr(native_codec, "available", lambda: False)
    fallback = decode(data)
    assert fallback.rgb.shape[:2] == (60, 40)
    np.testing.assert_array_equal(native.rgb, fallback.rgb)


def test_st0_metadata_carry_webp(tmp_path):
    """st_0 to/from WebP: ICC + EXIF survive via VP8X container surgery
    (jpeg->webp upgrades the simple container; webp source chunks are
    collected), and orientation is applied once then reset."""
    from PIL import Image as PILImage

    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.service.handler import ImageHandler
    from flyimg_tpu.storage import make_storage

    params = AppParameters(
        {"upload_dir": str(tmp_path / "u"), "tmp_dir": str(tmp_path / "t")}
    )
    handler = ImageHandler(make_storage(params), params)
    icc = _icc_profile_bytes()
    rng = np.random.default_rng(31)
    arr = rng.integers(0, 255, (90, 140, 3), dtype=np.uint8)
    img = PILImage.fromarray(arr)
    exif = img.getexif()
    exif[0x0112] = 6
    exif[0x010F] = "webp-cam"

    jpg_src = str(tmp_path / "s.jpg")
    img.save(jpg_src, "JPEG", quality=92, exif=exif, icc_profile=icc)
    webp_src = str(tmp_path / "s.webp")
    img.save(webp_src, "WEBP", quality=92, exif=exif, icc_profile=icc)

    for src, out_fmt in [
        (jpg_src, "webp"), (webp_src, "webp"), (webp_src, "jpg"),
    ]:
        result = handler.process_image(f"w_100,o_{out_fmt},st_0", src)
        out = PILImage.open(io.BytesIO(result.content))
        out.load()
        assert out.info.get("icc_profile") == icc, (src, out_fmt)
        carried = out.getexif()
        assert carried[0x010F] == "webp-cam", (src, out_fmt)
        assert carried.get(0x0112, 1) == 1, (src, out_fmt)
        # orientation 6 -> 90-degree rotation applied to the pixels
        assert out.size == (100, 156) or out.size[0] < out.size[1], (
            src, out_fmt, out.size,
        )


def test_metadata_parsers_survive_fuzzed_bytes():
    """The container parsers eat attacker-controlled bytes on every
    request; none of them may raise on garbage — malformed input means
    'no metadata', never a 500. Seeded structured fuzz: random bytes,
    truncations of valid files, and bit-flipped valid files."""
    from flyimg_tpu.codecs import metadata as m
    from flyimg_tpu.codecs.exif import jpeg_orientation, tiff_orientation

    rng = np.random.default_rng(99)
    icc = _icc_profile_bytes()
    base_jpg = encode(_img(seed=40), "jpg")
    base_png = encode(_img(seed=41), "png")
    base_webp = encode(_img(seed=42), "webp")

    meta = m.SourceMetadata(icc=icc, exif_tiff=b"II*\x00" + bytes(64))
    corpora = []
    for _ in range(60):
        corpora.append(rng.integers(0, 256, rng.integers(0, 400)).astype(
            np.uint8).tobytes())
    for base in (base_jpg, base_png, base_webp):
        for _ in range(40):
            cut = int(rng.integers(0, len(base)))
            corpora.append(base[:cut])
            flipped = bytearray(base)
            for _ in range(4):
                flipped[int(rng.integers(0, len(base)))] ^= int(
                    rng.integers(1, 256)
                )
            corpora.append(bytes(flipped))
    # adversarial prefixes that look like each container
    corpora += [
        b"\xff\xd8\xff\xe1\xff\xff",            # APP1 with huge length
        b"\x89PNG\r\n\x1a\n" + b"\xff" * 20,    # bad chunk length
        b"RIFF\xff\xff\xff\xffWEBP" + b"\x00" * 8,
    ]
    for blob in corpora:
        for mime in ("image/jpeg", "image/png", "image/webp"):
            got = m.collect(blob, mime)
            # inject into valid outputs must also never raise
            m.inject(base_jpg, "jpg", got)
            m.inject(base_png, "png", got)
            m.inject(base_webp, "webp", got)
        # and injecting VALID metadata into the fuzzed blob can't raise
        m.inject(blob, "jpg", meta)
        m.inject(blob, "png", meta)
        m.inject(blob, "webp", meta)
        assert 1 <= jpeg_orientation(blob) <= 8
        assert 1 <= tiff_orientation(blob) <= 8
        assert 1 <= m.png_orientation(blob) <= 8
        assert 1 <= m.webp_orientation(blob) <= 8


def test_native_cmyk_jpeg_decodes_like_pil():
    # print-origin (Adobe CMYK) JPEGs must ride the native decoder, not
    # silently fall to PIL (reference feeds them through IM transparently,
    # src/Core/Processor/ImageProcessor.php:68). PIL is the independent
    # oracle for the inverted-CMYK multiplicative fold.
    import io

    from PIL import Image

    from flyimg_tpu.codecs import decode, native_codec

    if not native_codec.available():
        pytest.skip("native codec not built")
    rgb = np.zeros((64, 96, 3), np.uint8)
    rgb[:, :32] = [255, 0, 0]
    rgb[:, 32:64] = [0, 255, 0]
    rgb[:, 64:] = [30, 60, 200]
    buf = io.BytesIO()
    Image.fromarray(rgb).convert("CMYK").save(buf, "JPEG", quality=95)
    data = buf.getvalue()

    out = native_codec.jpeg_decode(data, 8)
    assert out is not None, "CMYK fell off the native path"
    oracle = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
    assert out.shape == oracle.shape
    np.testing.assert_array_equal(out, oracle)

    # PIL's RGB->CMYK always writes K=0, which leaves the fold's k-term at
    # its identity point — hand-build planes with REAL black ink so the
    # c*k/255 multiply is exercised. atol 1: native truncates, Pillow's
    # MULDIV255 rounds.
    cmyk = np.zeros((32, 48, 4), np.uint8)
    cmyk[..., 0] = np.linspace(0, 255, 48, dtype=np.uint8)[None, :]
    cmyk[..., 1] = 80
    cmyk[..., 2] = 200
    cmyk[..., 3] = np.linspace(30, 220, 32, dtype=np.uint8)[:, None]
    buf2 = io.BytesIO()
    Image.frombytes("CMYK", (48, 32), cmyk.tobytes()).save(
        buf2, "JPEG", quality=95
    )
    data2 = buf2.getvalue()
    out2 = native_codec.jpeg_decode(data2, 8)
    assert out2 is not None
    oracle2 = np.asarray(
        Image.open(io.BytesIO(data2)).convert("RGB")
    ).astype(int)
    assert np.abs(out2.astype(int) - oracle2).max() <= 1
    # black ink really darkens: bottom rows (high K after inversion math)
    # must be darker than top rows
    assert out2[-1].mean() != out2[0].mean()

    # the facade path (what serving calls) returns the same pixels
    decoded = decode(data)
    np.testing.assert_array_equal(decoded.rgb, oracle)

    # and the pooled batch decoder (bulk/serving miss batches) agrees
    pool = native_codec.get_pool()
    if pool is not None:
        outs = pool.decode_batch([data, data], 8)
        for o in outs:
            assert o is not None
            np.testing.assert_array_equal(o, oracle)


# ---------------------------------------------------------------------------
# the loader builds from tracked sources (the binary is not in git)


def _private_native_dir(tmp_path, monkeypatch):
    """A copy of the native sources the loader can build in, with the
    process-wide load state reset and restored around the test."""
    import os
    import shutil

    src = native_codec._DIR
    dst = tmp_path / "native"
    dst.mkdir()
    for name in native_codec._SOURCES:
        shutil.copy2(os.path.join(src, name), dst / name)
    monkeypatch.setattr(native_codec, "_DIR", str(dst))
    monkeypatch.setattr(
        native_codec, "_LIB_PATH", str(dst / "libfastcodec.so")
    )
    monkeypatch.setattr(native_codec, "_lib", None)
    return dst


@pytest.mark.parametrize("found", ["missing", "unloadable", "stale", "older_sources"])
def test_loader_builds_library_from_tracked_sources(
    tmp_path, monkeypatch, found
):
    """Whatever is found where the library should be — nothing, another
    installation's binary that will not load here, a build older than its
    sources, or a newer file built from older sources (it loads, but lacks
    an entry point) — the loader ends up on a library built from the
    tracked sources. (One directory per case: dlopen resolves a path it has
    already loaded to the old handle.)"""
    import os
    import shutil
    import subprocess

    if not (shutil.which("make") and shutil.which("g++")):
        pytest.skip("no toolchain")
    assert native_codec.available()  # the checkout's own library exists
    built_here = native_codec._LIB_PATH
    dst = _private_native_dir(tmp_path, monkeypatch)
    lib = dst / "libfastcodec.so"
    if found == "unloadable":
        lib.write_bytes(b"\x7fELF not a library this machine can load")
        assert not native_codec._stale()  # newer than the sources
    elif found == "stale":
        shutil.copy2(built_here, lib)
        os.utime(lib, (1, 1))
    elif found == "older_sources":
        old = tmp_path / "old.cpp"
        old.write_text('extern "C" const char* fc_version() { return "0"; }\n')
        subprocess.run(["g++", "-shared", "-fPIC", "-o", str(lib), str(old)],
                       check=True)
        assert not native_codec._stale()  # newer than the sources
    if found not in ("unloadable", "older_sources"):
        assert native_codec._stale()
    assert native_codec._open_library() is not None
    assert not native_codec._stale()
    assert lib.stat().st_size > 10_000 and lib.stat().st_mtime > 1
    assert native_codec.available()


def test_loader_warns_once_when_it_ends_up_on_pil(
    tmp_path, monkeypatch, caplog
):
    import logging

    dst = _private_native_dir(tmp_path, monkeypatch)
    (dst / "libfastcodec.so").write_bytes(b"not loadable")
    monkeypatch.setattr(native_codec, "_build", lambda: False)
    with caplog.at_level(logging.WARNING, logger=native_codec.__name__):
        assert native_codec.available() is False
        assert native_codec.available() is False
    warnings = [
        r for r in caplog.records if "native host codec unavailable" in
        r.getMessage()
    ]
    assert len(warnings) == 1
    assert "PIL" in warnings[0].getMessage()
