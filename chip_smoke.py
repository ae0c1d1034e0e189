"""chip_smoke.py: does the serving path start and answer correctly on the chip?

Drives the main path once through the entry point a user calls —
``python -m flyimg_tpu.service.app serve`` -> handler -> host decode ->
batcher -> fused device program -> host encode — with real-size traffic at
the default server config, and checks what comes out by the repo's own means
(planned dimensions from ``flyimg_tpu.spec``, PSNR against Pillow LANCZOS,
the server's own /healthz, /metrics and /debug/plans). Then it starts a
second server against the same compile cache and repeats the first request:
nothing may be compiled again.

This process never imports jax: the server child is the one JAX process and
owns the chip. Sources are generated from a seed as local files; nothing
touches the network. Everything it writes goes under
``chiprun_out/chip_smoke/`` next to this file (server logs, params,
responses, ``report.json``).

Exit code 0 only when every check passed on an accelerator; stdout is then
two JSON lines: the full report (versions, per-check results, programs
compiled, set-up seconds, cache path), and last, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check — the device check included, so also every run pinned with
``JAX_PLATFORMS=cpu``, which runs the same steps for debugging — exits 1,
writes the report to stderr and ``report.json``, and prints the reason last.
No other switch.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
SEED = 21
BURST = 16
DEADLINE_S = 1100.0          # inside the 1200 s the contract allows
REQUEST_TIMEOUT_S = 600.0    # a cold request waits behind XLA compiles
PSNR_FLOOR_DB = 35.0         # the floor tests/test_ops.py holds on CPU

FLAGSHIP = "w_300,h_250,c_1"
_children: list = []


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    """A phase could not run at all (as opposed to a check that ran and
    failed, which is recorded and the run goes on)."""


# -- sources -----------------------------------------------------------------


def synth_image(width: int, height: int, seed: int):
    """A seeded photograph-like RGB image: smooth colour blobs (a small
    random field upsampled) plus fine noise."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    small = rng.integers(
        0, 256, (max(height // 48, 2), max(width // 48, 2), 3), dtype=np.uint8
    )
    blobs = np.asarray(
        Image.fromarray(small).resize((width, height), Image.BICUBIC),
        dtype=np.int16,
    )
    noise = rng.integers(-6, 7, (height, width, 3), dtype=np.int16)
    return Image.fromarray(np.clip(blobs + noise, 0, 255).astype(np.uint8))


def make_sources(directory: str) -> dict:
    os.makedirs(directory, exist_ok=True)
    sources = {"jpeg512": []}
    for i in range(BURST + 1):
        path = os.path.join(directory, f"photo_{i:02d}.jpg")
        synth_image(512, 512, SEED + i).save(path, quality=90)
        sources["jpeg512"].append(path)
    sources["png512"] = os.path.join(directory, "lossless_512.png")
    synth_image(512, 512, SEED + 100).save(sources["png512"])
    sources["jpeg12mp"] = os.path.join(directory, "large_4000x3000.jpg")
    large = synth_image(4000, 3000, SEED + 200)
    large.save(sources["jpeg12mp"], quality=90)
    # the same 12 MP frame as PNG: no DCT prescale on decode, so all 3000
    # rows reach the device (the H-sharded tiled path with several chips)
    sources["png12mp"] = os.path.join(directory, "large_4000x3000.png")
    large.save(sources["png12mp"], compress_level=1)
    return sources


# -- the server child --------------------------------------------------------


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


class Server:
    """One ``serve`` process with fresh storage under ``OUT/<name>``."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.dir = os.path.join(OUT, name)
        os.makedirs(self.dir)
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(self.dir, "server.log")
        params = os.path.join(self.dir, "params.yml")
        with open(params, "w", encoding="utf-8") as fh:
            # fresh storage: a storage hit never touches the device.
            # debug only opens the read-only /debug/plans endpoint the
            # report reads per-program compile seconds and peaks from.
            fh.write(
                f"upload_dir: {os.path.join(self.dir, 'uploads')}\n"
                f"tmp_dir: {os.path.join(self.dir, 'tmp')}\n"
                "debug: true\n"
            )
        self._log = open(self.log_path, "wb")
        self.started_at = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "flyimg_tpu.service.app", "serve",
             "--params", params, "--host", "127.0.0.1",
             "--port", str(self.port)],
            cwd=REPO, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        _children.append(self.proc)

    def log_tail(self, n: int = 30) -> str:
        with open(self.log_path, "r", encoding="utf-8", errors="replace") as fh:
            return "".join(fh.readlines()[-n:])

    def wait_healthy(self, timeout_s: float = 300.0) -> dict:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server {self.name} exited with code "
                    f"{self.proc.returncode} during boot:\n{self.log_tail()}"
                )
            try:
                status, _, body = self.get("/healthz", timeout=5)
                if status == 200:
                    return json.loads(body)
            except OSError:
                pass
            time.sleep(0.25)
        raise SmokeFailure(
            f"server {self.name} not healthy after {timeout_s:.0f}s:\n"
            f"{self.log_tail()}"
        )

    def get(self, path: str, timeout: float = REQUEST_TIMEOUT_S):
        try:
            with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
                return r.status, dict(r.headers), r.read()
        except urllib.error.HTTPError as exc:
            return exc.code, dict(exc.headers), exc.read()

    def render(self, options: str, source: str):
        return self.get(f"/upload/{options}/{source}")

    def metrics(self) -> dict:
        status, _, body = self.get("/metrics", timeout=30)
        if status != 200:
            raise SmokeFailure(f"/metrics answered {status}")
        values = {}
        for line in body.decode("utf-8").splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                try:
                    values[name] = float(value)
                except ValueError:
                    pass
        return values

    def stop(self) -> int:
        """SIGTERM, wait for the exit code; the whole process group is
        killed if it does not come."""
        try:
            self.proc.send_signal(signal.SIGTERM)
            code = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            code = None
        finally:
            kill_group(self.proc)
            self._log.close()
        return code


def kill_group(proc) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def cache_entries(directory) -> set:
    if not directory or not os.path.isdir(directory):
        return set()
    return {n for n in os.listdir(directory) if not n.endswith("-atime")}


# -- checks ------------------------------------------------------------------


class Report:
    def __init__(self) -> None:
        self.checks: dict = {}
        self.reasons: list = []

    def check(self, name: str, ok: bool, reason: str = "") -> bool:
        """Record one check; a name checked twice stays failed once it
        failed."""
        self.checks[name] = bool(ok) and self.checks.get(name, True)
        if not ok:
            self.reasons.append(f"{name}: {reason}")
            log(f"CHECK FAILED {name}: {reason}")
        return bool(ok)


def planned_size(options: str, src_w: int, src_h: int):
    """The output (w, h) the repo's own planner promises for a request."""
    from flyimg_tpu.spec import OptionsBag, build_plan

    return tuple(build_plan(OptionsBag(options), src_w, src_h).final_size)


def decoded(body: bytes):
    from PIL import Image

    image = Image.open(io.BytesIO(body))
    image.load()
    return image


def psnr(a, b) -> float:
    import numpy as np

    diff = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    mse = float(np.mean(diff * diff))
    return 99.0 if mse == 0 else 10.0 * float(np.log10(255.0 ** 2 / mse))


def expect_image(report, label, response, options, src_size, save_as=None,
                 post_crop=False):
    """200 + decodes to the planned dimensions — or, with ``post_crop``
    (smart crop is a post-pass the planner's ``final_size`` leaves out),
    to a non-empty window of them. Returns the PIL image or None."""
    status, _, body = response
    if save_as:
        with open(os.path.join(OUT, "responses", save_as), "wb") as fh:
            fh.write(body)
    if not report.check(
        "responses_ok", status == 200,
        f"{label}: HTTP {status} {body[:200]!r}",
    ):
        return None
    try:
        image = decoded(body)
    except Exception as exc:  # noqa: BLE001 - any decode failure is the finding
        report.check("responses_ok", False, f"{label}: undecodable ({exc})")
        return None
    want = planned_size(options, *src_size)
    if post_crop:
        fits = (0 < image.size[0] <= want[0] and 0 < image.size[1] <= want[1])
    else:
        fits = image.size == want
    report.check(
        "responses_ok", fits,
        f"{label}: decoded {image.size}, planned {want}",
    )
    return image


# -- the run -----------------------------------------------------------------


def first_run(report: Report, sources: dict, summary: dict) -> None:
    server = Server("run1")
    try:
        health = server.wait_healthy()
        summary["healthz"] = health
        devices = health.get("devices") or []
        platform = devices[0].split(":")[0] if devices else "none"
        summary["device"] = {
            "platform": platform,
            "kind": health.get("device_kind"),
            "count": len(devices),
        }
        log(f"server up on {devices} ({health.get('device_kind')}), "
            f"host codec {health.get('host_codec')}")
        report.check(
            "device_is_tpu",
            bool(devices) and all(d.startswith("tpu:") for d in devices),
            f"/healthz lists {devices}, want only tpu:* devices",
        )
        report.check(
            "native_codec", health.get("host_codec") == "native",
            f"host codec is {health.get('host_codec')!r}: decodes and "
            "encodes run on PIL",
        )

        # 1. the first request, alone: boot -> first 200 is the cold set-up
        jpegs = sources["jpeg512"]
        first = server.render(FLAGSHIP, jpegs[0])
        summary["cold_setup_s"] = round(
            time.monotonic() - server.started_at, 2
        )
        expect_image(report, "first", first, FLAGSHIP, (512, 512),
                     save_as="first.jpg")
        log(f"first 200 after {summary['cold_setup_s']}s (cold set-up)")

        # 2. a 16-way concurrent burst of distinct sources, twice
        with ThreadPoolExecutor(BURST) as pool:
            for label, options in (
                ("burst", FLAGSHIP), ("burst_smartcrop", FLAGSHIP + ",smc_1"),
            ):
                before = server.metrics()
                t0 = time.monotonic()
                responses = list(pool.map(
                    lambda src, o=options: server.render(o, src), jpegs[1:]
                ))
                after = server.metrics()
                for i, response in enumerate(responses):
                    expect_image(report, f"{label}[{i}]", response, options,
                                 (512, 512), save_as=f"{label}_{i:02d}.jpg",
                                 post_crop="smc_1" in options)
                launches = (after.get("flyimg_batches_total", 0)
                            - before.get("flyimg_batches_total", 0))
                images = (after.get("flyimg_images_processed_total", 0)
                          - before.get("flyimg_images_processed_total", 0))
                summary[label] = {
                    "launches": launches, "images": images,
                    "seconds": round(time.monotonic() - t0, 2),
                }
                log(f"{label}: {images:.0f} images in {launches:.0f} launches")
                report.check(
                    "batches_formed", images == BURST and 0 < launches < images,
                    f"{label}: {images:.0f} images in {launches:.0f} launches",
                )

        # 3. one request per remaining family
        singles = [
            ("large_w256", "w_256", sources["jpeg12mp"], (4000, 3000), "jpg"),
            ("large_cropfill_webp", "w_1200,h_800,c_1,o_webp",
             sources["jpeg12mp"], (4000, 3000), "webp"),
            ("large_png_w256", "w_256,o_png", sources["png12mp"],
             (4000, 3000), "png"),
            ("rotate", "w_400,h_400,r_-45,o_png", jpegs[0], (512, 512), "png"),
            ("filters", "w_256,blr_2x1,unsh_0.25x0.25+8+0.065,clsp_Gray,o_png",
             jpegs[0], (512, 512), "png"),
        ]
        for label, options, source, size, ext in singles:
            t0 = time.monotonic()
            expect_image(report, label, server.render(options, source),
                         options, size, save_as=f"{label}.{ext}")
            log(f"{label}: {time.monotonic() - t0:.1f}s")

        # 4. the lossless resize against Pillow LANCZOS
        from PIL import Image

        options = "w_256,o_png"
        got = expect_image(
            report, "lossless", server.render(options, sources["png512"]),
            options, (512, 512), save_as="lossless.png",
        )
        if got is not None:
            want = Image.open(sources["png512"]).convert("RGB").resize(
                (256, 256), Image.LANCZOS
            )
            if got.size == want.size:
                db = psnr(got.convert("RGB"), want)
                summary["lossless_psnr_db"] = round(db, 2)
                report.check(
                    "lossless_psnr", db >= PSNR_FLOOR_DB,
                    f"{db:.2f} dB against Pillow LANCZOS, floor "
                    f"{PSNR_FLOOR_DB}",
                )
        report.checks.setdefault("lossless_psnr", False)

        # 5. a repeat is a storage hit: no launch, same bytes
        before = server.metrics()
        again = server.render(FLAGSHIP, jpegs[0])
        after = server.metrics()
        hits = (after.get('flyimg_cache_total{result="hit"}', 0)
                - before.get('flyimg_cache_total{result="hit"}', 0))
        report.check(
            "repeat_is_cache_hit",
            again[0] == 200 and again[2] == first[2] and hits == 1
            and after.get("flyimg_batches_total")
            == before.get("flyimg_batches_total"),
            f"HTTP {again[0]}, {hits:.0f} cache hit(s), same bytes "
            f"{again[2] == first[2]}",
        )

        # 6. what the server counted
        metrics = server.metrics()
        summary["tiled_resamples"] = metrics.get(
            "flyimg_tiled_resamples_total", 0.0
        )
        report.check(
            "batches_formed", metrics.get("flyimg_batches_total", 0) > 0,
            "flyimg_batches_total is 0",
        )
        tripped = {
            name: metrics[name] for name in (
                "flyimg_wedged_fallbacks_total", "flyimg_plan_uncosted",
            ) if metrics.get(name)
        }
        report.check("no_fallbacks", not tripped, f"non-zero: {tripped}")
        errors = {
            name: value for name, value in metrics.items()
            if name.startswith("flyimg_requests_total") and 'status="5' in name
            and value
        }
        report.check("no_5xx", not errors, f"{errors}")

        # 7. every program the traffic built: compile seconds and the
        # memory_analysis() peak, as the cost ledger recorded them
        status, _, body = server.get("/debug/plans", timeout=30)
        plans = json.loads(body) if status == 200 else {}
        programs = [
            {
                "ops": p["descriptor"].get("ops"),
                "in_shape": p["descriptor"].get("in_shape"),
                "batch": p["descriptor"].get("batch"),
                "compile_s": p.get("compile_s"),
                "peak_memory_bytes": p.get("peak_memory_bytes"),
                "devices": p.get("devices"),
                "launches": p.get("launches"),
                "images": p.get("images"),
            }
            for p in plans.get("plans", [])
        ]
        summary["programs"] = programs
        summary["programs_compiled"] = int(
            (plans.get("aggregates") or {}).get("compiles", 0)
        )
        summary["compile_seconds"] = round(
            (plans.get("aggregates") or {}).get("compile_seconds", 0.0), 2
        )
        peaks = [p["peak_memory_bytes"] for p in programs
                 if p["peak_memory_bytes"]]
        summary["largest_peak_memory_bytes"] = max(peaks) if peaks else None
        report.check(
            "programs_compiled",
            summary["programs_compiled"] > 0
            and len(peaks) == len(programs) > 0,
            f"{summary['programs_compiled']} compiles, "
            f"{len(peaks)}/{len(programs)} programs with a memory peak",
        )
    finally:
        code = server.stop()
    report.check("clean_exit", code == 0, f"run1 exit code {code} on SIGTERM")


def second_run(report: Report, sources: dict, summary: dict,
               cache_dir) -> None:
    """A second start against the same compile cache, fresh storage: the
    first request again. The cache gains no entry."""
    after_first = cache_entries(cache_dir)
    summary["compile_cache_entries"] = len(after_first)
    server = Server("run2")
    try:
        server.wait_healthy()
        response = server.render(FLAGSHIP, sources["jpeg512"][0])
        summary["warm_setup_s"] = round(
            time.monotonic() - server.started_at, 2
        )
        expect_image(report, "warm first", response, FLAGSHIP, (512, 512))
        log(f"first 200 after {summary['warm_setup_s']}s (warm set-up)")
    finally:
        code = server.stop()
    report.check("clean_exit", code == 0, f"run2 exit code {code} on SIGTERM")
    gained = cache_entries(cache_dir) - after_first
    summary["compile_cache_entries_gained_run2"] = len(gained)
    report.check(
        "compile_cache_reused", bool(after_first) and not gained,
        f"{len(after_first)} entries after run one, run two added "
        f"{len(gained)}: {sorted(gained)[:4]}",
    )


def versions() -> dict:
    from importlib import metadata

    found = {}
    for name in ("jax", "jaxlib", "libtpu"):
        try:
            found[name] = metadata.version(name)
        except metadata.PackageNotFoundError:
            found[name] = None
    return found


def result_line(device: dict) -> str:
    """The last stdout line of a passing run: these keys and no others
    (the full report is the line before it and ``report.json``)."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": int(device["count"]),
    }})


def main() -> int:
    if not os.path.isdir(os.path.join(REPO, "flyimg_tpu")):
        print("chip_smoke: FAILED: the flyimg_tpu package is not next to "
              "this script", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    from flyimg_tpu.compilecache import compile_cache_dir

    def abort() -> None:
        log(f"FAILED: not done after {DEADLINE_S:.0f}s")
        for proc in _children:
            kill_group(proc)
        os._exit(3)

    watchdog = threading.Timer(DEADLINE_S, abort)
    watchdog.daemon = True
    watchdog.start()

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(os.path.join(OUT, "responses"))
    cache_dir = compile_cache_dir()
    report = Report()
    summary: dict = {
        "device": {"platform": "none", "kind": None, "count": 0},
        "versions": versions(),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": len(cache_entries(cache_dir)),
    }
    t0 = time.monotonic()
    try:
        sources = make_sources(os.path.join(OUT, "sources"))
        log(f"sources ready after {time.monotonic() - t0:.1f}s")
        first_run(report, sources, summary)
        second_run(report, sources, summary, cache_dir)
    except SmokeFailure as exc:
        report.check("ran_to_end", False, str(exc))
    finally:
        for proc in _children:
            kill_group(proc)
        watchdog.cancel()
    summary["seconds"] = round(time.monotonic() - t0, 1)

    ok = bool(report.checks) and all(report.checks.values())
    device = summary.pop("device")
    line = {
        "ok": ok,
        "device": device,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        "checks": report.checks,
        **{k: v for k, v in summary.items()
           if k not in ("programs", "healthz")},
    }
    with open(os.path.join(OUT, "report.json"), "w", encoding="utf-8") as fh:
        json.dump({**line, "programs": summary.get("programs"),
                   "healthz": summary.get("healthz"),
                   "failures": report.reasons}, fh, indent=1)
        fh.write("\n")
    if ok:
        print(json.dumps(line))
        print(result_line(device), flush=True)
        return 0
    # no result on stdout: the report and the reason go to stderr
    print(json.dumps(line), file=sys.stderr)
    print("chip_smoke: FAILED: " + "; ".join(report.reasons).rstrip(),
          file=sys.stderr, flush=True)
    return 1


if __name__ == "__main__":
    sys.exit(main())
