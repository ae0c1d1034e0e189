"""HTTP-level conformance tests.

Mirror of the reference's controller suite
(reference tests/Core/Controller/DefaultControllerTest.php): real GETs
against the app — homepage, upload, path, content negotiation, refresh
debug headers, error-status mapping — plus this framework's observability
routes (/metrics, /healthz) which have no reference analog.

Local file paths stand in for source URLs exactly as in the reference suite
(reference tests/Core/BaseTest.php uses fixture paths as imageSrc).
"""

import asyncio
import contextlib
import os

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from flyimg_tpu.appconfig import AppParameters
from flyimg_tpu.codecs import decode, encode
from flyimg_tpu.service.app import make_app


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.fixture()
def source_png(tmp_path):
    rng = np.random.default_rng(7)
    img = rng.integers(0, 255, (64, 80, 3), dtype=np.uint8)
    path = tmp_path / "source.png"
    path.write_bytes(encode(img, "png"))
    return str(path)


def _params(tmp_path, **extra):
    base = {
        "tmp_dir": str(tmp_path / "tmp"),
        "upload_dir": str(tmp_path / "uploads"),
        "batch_deadline_ms": 1.0,
    }
    base.update(extra)
    return AppParameters(base)


def _request(tmp_path, path, *, headers=None, params_extra=None):
    """One request against a fresh app; returns (status, headers, body)."""

    async def go():
        app = make_app(_params(tmp_path, **(params_extra or {})))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get(path, headers=headers or {})
            body = await resp.read()
            return resp.status, dict(resp.headers), body
        finally:
            await client.close()

    return _run(go())


def test_homepage(tmp_path):
    status, headers, body = _request(tmp_path, "/")
    assert status == 200
    assert b"flyimg" in body


def test_upload_serves_image_with_cache_headers(tmp_path, source_png):
    status, headers, body = _request(
        tmp_path, f"/upload/w_32,h_24,c_1,o_png/{source_png}"
    )
    assert status == 200
    assert headers["Content-Type"] == "image/png"
    assert "max-age" in headers["Cache-Control"]
    assert headers["X-Content-Type-Options"] == "nosniff"
    out = decode(body)
    # c_1 = crop-fill: exact target box (reference ImageProcessor.php:138-148)
    assert (out.rgb.shape[1], out.rgb.shape[0]) == (32, 24)


def test_last_modified_tracks_stored_artifact(tmp_path, source_png):
    """Last-Modified is the stored artifact's mtime (reference
    Response.php:72-78), so repeated cache hits serve a STABLE value
    instead of re-stamping now() on every request."""
    import email.utils
    import os
    import time

    path = f"/upload/w_32,o_png/{source_png}"
    _, h1, _ = _request(tmp_path, path)
    time.sleep(1.1)  # HTTP-date is second-granular
    _, h2, _ = _request(tmp_path, path)  # cache hit in the same upload_dir
    assert h1["Last-Modified"] == h2["Last-Modified"]
    stored = next(
        (tmp_path / "uploads").glob("*.png")
    )
    assert email.utils.parsedate_to_datetime(
        h2["Last-Modified"]
    ).timestamp() == int(os.path.getmtime(stored))


def test_conditional_requests_get_304(tmp_path, source_png):
    """ETag (the content-addressed name) + If-None-Match / Last-Modified +
    If-Modified-Since answer 304 with no body — revalidation never re-reads
    or re-serves the bytes (beyond-reference: flyimg sends validators but
    always re-serves 200s)."""
    path = f"/upload/w_32,o_png/{source_png}"
    _, h1, body1 = _request(tmp_path, path)
    etag = h1["Etag"]  # aiohttp title-cases header names on the wire
    assert etag.startswith('"') and len(body1) > 0

    status, h2, body2 = _request(
        tmp_path, path, headers={"If-None-Match": etag}
    )
    assert status == 304 and body2 == b""
    assert h2["Etag"] == etag  # 304 carries validators (RFC 9110)

    status, _, body3 = _request(
        tmp_path, path, headers={"If-Modified-Since": h1["Last-Modified"]}
    )
    assert status == 304 and body3 == b""

    status, _, body4 = _request(
        tmp_path, path, headers={"If-None-Match": '"nope"'}
    )
    assert status == 200 and body4 == body1

    # rf_1 is an explicit recompute: conditionals never shortcut it
    status, _, body5 = _request(
        tmp_path,
        f"/upload/w_32,o_png,rf_1/{source_png}",
        headers={"If-None-Match": etag},
    )
    assert status == 200 and len(body5) > 0


def test_upload_webp_negotiation(tmp_path, source_png):
    status, headers, _ = _request(
        tmp_path,
        f"/upload/w_20,o_auto/{source_png}",
        headers={"Accept": "image/webp,image/png"},
    )
    assert status == 200
    assert headers["Content-Type"] == "image/webp"
    # Accept decided the body -> shared caches must key on it
    assert headers["Vary"] == "Accept"

    # explicit output format: no negotiation, no Vary
    status, headers, _ = _request(
        tmp_path, f"/upload/w_20,o_png/{source_png}"
    )
    assert status == 200 and "Vary" not in headers


def test_upload_refresh_debug_headers(tmp_path, source_png):
    status, headers, _ = _request(
        tmp_path, f"/upload/w_20,o_jpg,rf_1/{source_png}"
    )
    assert status == 200
    assert "no-cache" in headers["Cache-Control"]
    assert "im-command" in headers  # reference Response.php:58-64
    assert "x-flyimg-timings" in headers
    # reference Response.php:62: the output's `identify` line
    assert "im-identify" in headers
    assert "JPEG 20x" in headers["im-identify"]


def test_path_route_returns_public_url(tmp_path, source_png):
    status, _, body = _request(tmp_path, f"/path/w_20,o_jpg/{source_png}")
    assert status == 200
    assert body.decode().startswith("http")
    assert "/uploads/" in body.decode()


def test_missing_source_404(tmp_path):
    status, _, body = _request(tmp_path, "/upload/w_20/nonexistent-file.jpg")
    assert status == 404
    assert b"ReadFileException" in body


def test_invalid_output_extension_400(tmp_path, source_png):
    status, _, body = _request(tmp_path, f"/upload/o_xxx/{source_png}")
    assert status == 400
    assert b"InvalidArgumentException" in body


def test_resilience_error_status_mapping(tmp_path):
    """DeadlineExceededException -> 504; ServiceUnavailableException ->
    503 carrying Retry-After from the exception's retry_after_s
    (runtime/resilience.py admission/breaker shed)."""
    from flyimg_tpu.exceptions import (
        DeadlineExceededException,
        ServiceUnavailableException,
    )
    from flyimg_tpu.service.app import HANDLER_KEY

    def hit_with(exc):
        async def go():
            app = make_app(_params(tmp_path))
            app[HANDLER_KEY].process_image = (
                lambda *a, **k: (_ for _ in ()).throw(exc)
            )
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                resp = await client.get("/upload/w_20/ignored.png")
                return resp.status, dict(resp.headers), await resp.text()
            finally:
                await client.close()

        return _run(go())

    status, headers, body = hit_with(DeadlineExceededException("budget"))
    assert status == 504
    assert "DeadlineExceededException" in body
    assert "Retry-After" not in headers  # 504 is not an invitation to hammer

    shed = ServiceUnavailableException("queue full")
    shed.retry_after_s = 5
    status, headers, body = hit_with(shed)
    assert status == 503
    assert headers["Retry-After"] == "5"
    assert "ServiceUnavailableException" in body

    # the class default applies when nothing set a specific value
    status, headers, _ = hit_with(ServiceUnavailableException("wedged"))
    assert status == 503 and headers["Retry-After"] == "1"


def test_restricted_domain_403(tmp_path):
    status, _, body = _request(
        tmp_path,
        "/upload/w_20/http://evil.example.com/x.jpg",
        params_extra={
            "restricted_domains": True,
            "whitelist_domains": ["good.example.com"],
        },
    )
    assert status == 403
    assert b"SecurityException" in body


def test_signed_url_flow(tmp_path, source_png):
    """With a security key set, the options segment carries the encrypted
    '{options}/{imageSrc}' token (reference SecurityHandler.php:58-88)."""
    pytest.importorskip("cryptography")
    from flyimg_tpu.service.security import encrypt

    key, iv = "test-key", "test-iv"
    token = encrypt(f"w_32,h_24,o_png/{source_png}", key, iv)
    if "/" in token:
        pytest.skip("token contains '/'; route-split quirk shared with reference")
    extra = {"security_key": key, "security_iv": iv}
    status, headers, _ = _request(
        tmp_path, f"/upload/{token}/ignored", params_extra=extra
    )
    assert status == 200
    assert headers["Content-Type"] == "image/png"

    # an unsigned request under a security key must 403
    status, _, _ = _request(
        tmp_path, f"/upload/w_32/{source_png}", params_extra=extra
    )
    assert status == 403


def test_metrics_and_healthz(tmp_path, source_png):
    async def go():
        app = make_app(_params(tmp_path))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await client.get(f"/upload/w_20,o_jpg/{source_png}")
            metrics = await (await client.get("/metrics")).text()
            health = await (await client.get("/healthz")).json()
            return metrics, health
        finally:
            await client.close()

    metrics, health = _run(go())
    assert 'flyimg_requests_total{route="upload",status="200"} 1' in metrics
    assert 'flyimg_cache_total{result="miss"} 1' in metrics
    assert "flyimg_stage_seconds" in metrics
    assert health["status"] == "ok"
    assert health["devices"]


def test_route_patterns_config_overridable(tmp_path, source_png):
    """The route table is config-driven like the reference's routes.yml."""
    status, _, _ = _request(
        tmp_path,
        f"/img/w_30,o_png/{source_png}",
        params_extra={"routes": {"upload": "/img/{options}/{imageSrc:.+}"}},
    )
    assert status == 200
    status, _, _ = _request(
        tmp_path,
        f"/upload/w_30,o_png/{source_png}",
        params_extra={"routes": {"upload": "/img/{options}/{imageSrc:.+}"}},
    )
    assert status == 404


@contextlib.contextmanager
def _restoring_jax_cache_config():
    """make_app / enable_compile_cache mutate process-global jax config;
    restore it so later tests don't write cache artifacts elsewhere."""
    import jax

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    saved = {name: getattr(jax.config, name) for name in names}
    try:
        yield
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)


def test_compilation_cache_env_var_wins_and_code_sets_no_dir(
    tmp_path, monkeypatch
):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself: the
    helper reports it and sets no directory in code — whatever the knob
    says, '' included."""
    import jax

    from flyimg_tpu import compilecache

    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (updates.append(name), real_update(name, value)),
    )
    with _restoring_jax_cache_config():
        for knob in ("var/cache/xla", str(tmp_path / "knob"), ""):
            assert compilecache.compile_cache_dir(knob) == placed
            assert compilecache.enable_compile_cache(knob) == placed
        assert "jax_compilation_cache_dir" not in updates
    assert not (tmp_path / "knob").exists()


def test_compilation_cache_default_is_the_checkout_whatever_the_cwd(
    tmp_path, monkeypatch
):
    """Unset, the cache is <checkout>/var/cache/xla resolved from the
    package, not from the current directory; make_app arms exactly that.
    An absolute knob is honored, '' disables."""
    import jax

    from flyimg_tpu import compilecache
    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.service.app import make_app

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expected = os.path.join(repo, "var", "cache", "xla")
    assert compilecache.compile_cache_dir() == expected
    assert compilecache.compile_cache_dir("") is None
    elsewhere = str(tmp_path / "xla-cache")
    assert compilecache.compile_cache_dir(elsewhere) == elsewhere
    params = AppParameters(
        {"upload_dir": str(tmp_path / "u"), "tmp_dir": str(tmp_path / "t")}
    )
    with _restoring_jax_cache_config():
        app = make_app(params)
        try:
            assert jax.config.jax_compilation_cache_dir == expected
            assert not (tmp_path / "var").exists()
        finally:
            async def cleanup():
                for cb in app.on_cleanup:
                    await cb(app)

            _run(cleanup())
        assert compilecache.enable_compile_cache(elsewhere) == elsewhere
        assert os.path.isdir(elsewhere)
        assert jax.config.jax_compilation_cache_dir == elsewhere


def test_refresh_mints_new_etag(tmp_path, source_png):
    """The ETag folds in the stored artifact's mtime: an rf_1 rewrite of
    the SAME name must produce a different validator, or revalidating
    CDNs would 304 into stale bytes after the content changed."""
    import time

    path = f"/upload/w_32,o_png/{source_png}"
    _, h1, _ = _request(tmp_path, path)
    time.sleep(1.1)  # mtime + HTTP-date are second-granular
    _, h2, _ = _request(tmp_path, f"/upload/w_32,o_png,rf_1/{source_png}")
    _, h3, _ = _request(tmp_path, path)  # post-refresh cache hit
    assert h2["Etag"] != h1["Etag"]
    assert h3["Etag"] == h2["Etag"]  # stable again after the rewrite
    # the old validator no longer matches -> full 200, fresh bytes
    status, _, body = _request(
        tmp_path, path, headers={"If-None-Match": h1["Etag"]}
    )
    assert status == 200 and len(body) > 0


def test_background_prune_enforces_cache_budget(tmp_path, source_png):
    """With cache_max_bytes set, serve prunes the upload dir in the
    background: old artifacts beyond the budget disappear without any
    operator action."""
    import asyncio
    import os
    import time

    async def go():
        app = make_app(
            _params(
                tmp_path,
                cache_max_bytes=1,           # everything overflows
                cache_prune_interval_s=0.2,
            )
        )
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get(f"/upload/w_32,o_png/{source_png}")
            assert resp.status == 200
            # don't pre-assert the artifact exists: the pruner runs in a
            # real executor thread and may already have evicted it
            up = tmp_path / "uploads"
            deadline = time.time() + 5
            while time.time() < deadline and os.listdir(up):
                await asyncio.sleep(0.1)
            assert os.listdir(up) == []
        finally:
            await client.close()

    _run(go())
