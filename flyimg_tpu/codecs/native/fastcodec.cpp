// fastcodec: native host codec layer for flyimg-tpu.
//
// The TPU-native replacement for the reference's codec binaries — the decode
// half of ImageMagick `convert` and the encode side of MozJPEG `cjpeg` /
// `cwebp` (reference src/Core/Processor/Processor.php:15-33 hard-codes those
// binary paths; here the same work is an in-process library so image bytes
// never cross a process boundary on the way to the device).
//
// Design:
//  - Plain C ABI (ctypes-friendly), all buffers malloc'd here and released
//    via fc_free (a frame the pool keeps, via fc_pool_release); no global
//    state, safe to call from many threads at once.
//  - JPEG via libjpeg(-turbo): decode with optional DCT scaling
//    (scale 1/1..1/8 — the decode-time prescale that feeds 4k sources to
//    thumbnail pipelines cheaply); two encoders — a plain optimized one
//    and fc_jpeg_encode_trellis, which adds trellis quantization to the
//    optimized-Huffman + progressive pair (the full MozJPEG technique set;
//    measured ~5-10% smaller at ~equal PSNR on photographic content).
//  - WebP via libwebp: lossy (quality) and lossless encode, decode to RGB.
//  - A worker pool (fc_pool_*) so a multi-core host can saturate decode
//    while the GIL is released on the Python side. The pool keeps the
//    output buffers of large full frames (fc_frame_pool) and hands them to
//    later frames, which are given back through fc_pool_release.

#include <cmath>
#include <csetjmp>
#include <cstdint>
#include <cstdio>  // jpeglib.h needs FILE declared
#include <cstdlib>
#include <cstring>

#include <jpeglib.h>
#include <libdeflate.h>
#if defined(__SSE2__)
#include <emmintrin.h>
#endif
#include <png.h>
#if defined(__has_include)
#if __has_include(<webp/decode.h>)
#include <webp/decode.h>
#include <webp/encode.h>
#else
// runtime-only libwebp host (library present, -dev headers absent):
// declare the handful of entry points we use against the stable .so.6 ABI
#include "webp_shim.h"
#endif
#else
#include <webp/decode.h>
#include <webp/encode.h>
#endif

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <functional>
#include <mutex>
#include <queue>
#include <thread>
#include <type_traits>
#include <utility>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// common
// ---------------------------------------------------------------------------

void fc_free(void* ptr) { std::free(ptr); }

const char* fc_version() { return "fastcodec-1.0"; }

// ---------------------------------------------------------------------------
// JPEG
// ---------------------------------------------------------------------------

struct fc_jpeg_error_mgr {
  jpeg_error_mgr pub;
  jmp_buf setjmp_buffer;
};

static void fc_jpeg_error_exit(j_common_ptr cinfo) {
  auto* err = reinterpret_cast<fc_jpeg_error_mgr*>(cinfo->err);
  longjmp(err->setjmp_buffer, 1);
}

// ---------------------------------------------------------------------------
// Frame buffers kept for reuse. A full-frame decode at least
// kFramePoolMinBytes long writes into a buffer an earlier frame already
// touched, and the buffer goes back to the pool, not to free(), when its
// last view goes. glibc serves an allocation of DEFAULT_MMAP_THRESHOLD_MAX
// (32 MiB on 64-bit) or more from a fresh mmap whatever its dynamic
// threshold has risen to: the kernel faults and zeroes every page while the
// decoder writes the rows, and free() unmaps the frame, taking the address
// space's lock and shooting down TLBs on every core the process runs on.
// Smaller frames (ROI windows, prescaled thumbnails, frames under some
// 11 MP) come from the heap, whose pages are reused already; they keep
// malloc and fc_free.
// ---------------------------------------------------------------------------

static const size_t kFramePoolMinBytes = size_t{32} << 20;

// An idle buffer no frame took for this long is freed: a process that
// falls quiet after a burst gives the burst's frames back. Three cycles of
// the slowest measured launch (6-7 s), so a steady stream keeps its own.
static const int64_t kFrameIdleMs = 20000;

// How often a worker with nothing to do frees what has aged out.
static const std::chrono::milliseconds kFrameTrimEvery{1000};

using FrameClock = std::chrono::steady_clock;

struct fc_frame_pool {
  struct Idle {
    uint8_t* ptr;
    size_t cap;
    FrameClock::time_point since;  // when it was given back
  };
  std::mutex mu;
  std::vector<Idle> idle;  // in the order given back: the oldest first
  size_t idle_bytes = 0;
  size_t live = 0;  // pooled buffers handed out and not yet given back
  size_t live_bytes = 0;
  // the most bytes live at once: idle_bytes + live_bytes never exceeds it,
  // so the pool holds no more than the process held at its own peak
  size_t peak_bytes = 0;
  std::atomic<size_t> min_bytes{kFramePoolMinBytes};
  std::atomic<int64_t> idle_ms{kFrameIdleMs};
  bool closed = false;  // its fc_pool is gone: buffers given back are freed
};

// Under fp->mu: the idle buffers given back more than idle_ms ago leave
// the list into *stale, for the caller to free outside the lock.
static void frame_age_locked(fc_frame_pool* fp, std::vector<uint8_t*>* stale) {
  const auto oldest =
      FrameClock::now() - std::chrono::milliseconds(fp->idle_ms.load());
  size_t n = 0;
  while (n < fp->idle.size() && fp->idle[n].since < oldest) {
    stale->push_back(fp->idle[n].ptr);
    fp->idle_bytes -= fp->idle[n].cap;
    ++n;
  }
  fp->idle.erase(fp->idle.begin(), fp->idle.begin() + n);
}

// Free the idle buffers that have aged out.
static void frame_trim(fc_frame_pool* fp) {
  std::vector<uint8_t*> stale;
  {
    std::lock_guard<std::mutex> lock(fp->mu);
    frame_age_locked(fp, &stale);
  }
  for (uint8_t* ptr : stale) std::free(ptr);
}

// A buffer for a full frame of nbytes. Below the pool's size (or with no
// pool) plain malloc and *cap 0; else the smallest idle buffer that holds
// the frame and is at most twice its size (*reused 1), or a new one
// (*reused 0), with *cap its capacity.
static uint8_t* frame_take(fc_frame_pool* fp, size_t nbytes, size_t* cap,
                           int* reused) {
  *cap = 0;
  *reused = 0;
  if (fp == nullptr || nbytes < fp->min_bytes) {
    return static_cast<uint8_t*>(std::malloc(nbytes));
  }
  std::vector<uint8_t*> spill;
  {
    std::lock_guard<std::mutex> lock(fp->mu);
    // of the smallest that fit, the last in the list (the one given back
    // last: its pages are the likeliest cached), so a surplus the cycle
    // does not need stays at the front and ages out
    size_t best = fp->idle.size();
    for (size_t i = 0; i < fp->idle.size(); ++i) {
      const size_t c = fp->idle[i].cap;
      if (c >= nbytes && c - nbytes <= nbytes &&
          (best == fp->idle.size() || c <= fp->idle[best].cap)) {
        best = i;
      }
    }
    ++fp->live;
    if (best < fp->idle.size()) {
      uint8_t* ptr = fp->idle[best].ptr;
      *cap = fp->idle[best].cap;
      *reused = 1;
      fp->idle_bytes -= *cap;
      fp->live_bytes += *cap;
      fp->idle.erase(fp->idle.begin() + best);
      return ptr;
    }
    fp->live_bytes += nbytes;
    if (fp->live_bytes > fp->peak_bytes) fp->peak_bytes = fp->live_bytes;
    // the new buffer makes room for itself: the oldest idle ones go until
    // idle + live is back within the most that was ever live
    size_t n = 0;
    while (n < fp->idle.size() &&
           fp->idle_bytes + fp->live_bytes > fp->peak_bytes) {
      spill.push_back(fp->idle[n].ptr);
      fp->idle_bytes -= fp->idle[n].cap;
      ++n;
    }
    fp->idle.erase(fp->idle.begin(), fp->idle.begin() + n);
  }
  for (uint8_t* ptr : spill) std::free(ptr);
  uint8_t* out = static_cast<uint8_t*>(std::malloc(nbytes));
  if (out == nullptr) {
    std::lock_guard<std::mutex> lock(fp->mu);
    --fp->live;
    fp->live_bytes -= nbytes;
    return nullptr;
  }
  *cap = nbytes;
  return out;
}

// Give back a frame's buffer: cap 0 is free(); a pooled one goes idle (and
// what has aged out goes), or is freed once the pool is closed (the last
// one deletes the pool).
static void frame_give(fc_frame_pool* fp, uint8_t* ptr, size_t cap) {
  if (cap == 0) {
    std::free(ptr);
    return;
  }
  std::vector<uint8_t*> stale;
  bool last = false;
  {
    std::lock_guard<std::mutex> lock(fp->mu);
    --fp->live;
    fp->live_bytes -= cap;
    if (!fp->closed) {
      fp->idle.push_back({ptr, cap, FrameClock::now()});
      fp->idle_bytes += cap;
      frame_age_locked(fp, &stale);
      ptr = nullptr;
    } else {
      last = fp->live == 0;
    }
  }
  for (uint8_t* old : stale) std::free(old);
  std::free(ptr);
  if (last) delete fp;
}

// The full-frame decode behind fc_jpeg_decode and the pool's batches: the
// output buffer comes from frame_take (``frames`` null: malloc) and its
// capacity back in *cap (0: fc_free releases it). Every row is written or
// the decode fails, so a reused buffer never shows an earlier frame.
static uint8_t* decode_full(const uint8_t* data, size_t len, int scale_num,
                            int* width, int* height, fc_frame_pool* frames,
                            size_t* cap, int* reused) {
  jpeg_decompress_struct cinfo;
  fc_jpeg_error_mgr jerr;
  cinfo.err = jpeg_std_error(&jerr.pub);
  jerr.pub.error_exit = fc_jpeg_error_exit;
  // volatile: these are modified between setjmp and a potential longjmp;
  // without it the error path would free indeterminate (register-cached)
  // values — double-free or leak (C11 7.13.2.1p2)
  uint8_t* volatile out = nullptr;
  volatile size_t out_cap = 0;
  uint8_t* volatile row4 = nullptr;  // CMYK scanline scratch
  if (setjmp(jerr.setjmp_buffer)) {
    jpeg_destroy_decompress(&cinfo);
    if (out) frame_give(frames, out, out_cap);
    std::free(row4);
    return nullptr;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, data, len);
  if (jpeg_read_header(&cinfo, TRUE) != JPEG_HEADER_OK) {
    jpeg_destroy_decompress(&cinfo);
    return nullptr;
  }
  const bool cmyk = cinfo.jpeg_color_space == JCS_CMYK ||
                    cinfo.jpeg_color_space == JCS_YCCK;
  cinfo.out_color_space = cmyk ? JCS_CMYK : JCS_RGB;
  // Adobe writers store CMYK inverted (byte = 255 - ink); YCCK is defined
  // over the inverted planes, so treat it as inverted even on the rare
  // file missing its APP14 marker. Same policy as IM/libjpeg-turbo tools.
  const bool inverted = cinfo.saw_Adobe_marker ||
                        cinfo.jpeg_color_space == JCS_YCCK;
  if (scale_num >= 1 && scale_num <= 8) {
    cinfo.scale_num = scale_num;
    cinfo.scale_denom = 8;
  }
  // fastest safe knobs: merged upsampling stays on by default
  cinfo.do_fancy_upsampling = TRUE;
  jpeg_start_decompress(&cinfo);
  const int w = cinfo.output_width;
  const int h = cinfo.output_height;
  const int stride = w * 3;
  size_t taken_cap = 0;
  out = frame_take(frames, static_cast<size_t>(stride) * h, &taken_cap,
                   reused);
  out_cap = taken_cap;
  if (!out) {
    jpeg_abort_decompress(&cinfo);
    jpeg_destroy_decompress(&cinfo);
    return nullptr;
  }
  if (cmyk) {
    row4 = static_cast<uint8_t*>(std::malloc(static_cast<size_t>(w) * 4));
    if (!row4) {
      jpeg_abort_decompress(&cinfo);
      jpeg_destroy_decompress(&cinfo);
      frame_give(frames, out, out_cap);
      return nullptr;
    }
  }
  while (cinfo.output_scanline < cinfo.output_height) {
    uint8_t* row = out + static_cast<size_t>(cinfo.output_scanline) * stride;
    JSAMPROW rows[1] = {cmyk ? row4 : row};
    if (jpeg_read_scanlines(&cinfo, rows, 1) != 1) {
      // no row came back: fail rather than leave the rest of the buffer
      // as it was (a reused buffer holds an earlier frame)
      std::free(row4);
      jpeg_abort_decompress(&cinfo);
      jpeg_destroy_decompress(&cinfo);
      frame_give(frames, out, out_cap);
      return nullptr;
    }
    if (!cmyk) continue;
    // multiplicative fold: R = (255-C)*(255-K)/255 over real ink values;
    // with Adobe's inverted storage the (255 - s) terms cancel to s*k/255
    for (int x = 0; x < w; ++x) {
      const int c = row4[x * 4 + 0], m = row4[x * 4 + 1];
      const int y = row4[x * 4 + 2], k = row4[x * 4 + 3];
      if (inverted) {
        row[x * 3 + 0] = static_cast<uint8_t>(c * k / 255);
        row[x * 3 + 1] = static_cast<uint8_t>(m * k / 255);
        row[x * 3 + 2] = static_cast<uint8_t>(y * k / 255);
      } else {
        row[x * 3 + 0] = static_cast<uint8_t>((255 - c) * (255 - k) / 255);
        row[x * 3 + 1] = static_cast<uint8_t>((255 - m) * (255 - k) / 255);
        row[x * 3 + 2] = static_cast<uint8_t>((255 - y) * (255 - k) / 255);
      }
    }
  }
  std::free(row4);
  row4 = nullptr;
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  *width = w;
  *height = h;
  *cap = out_cap;
  return out;
}

// Decode a JPEG buffer to RGB. scale_num/8 is the libjpeg DCT scale
// (pass 8 for full size, 4 for 1/2, 2 for 1/4, 1 for 1/8).
// CMYK and YCCK (Adobe print-origin) sources decode natively: libjpeg
// hands back CMYK samples (it converts YCCK->CMYK itself but cannot emit
// RGB from a CMYK family), and the multiplicative CMYK->RGB fold happens
// here — the reference feeds such JPEGs through ImageMagick transparently
// (src/Core/Processor/ImageProcessor.php:68), so the native path must not
// silently punt them to the slow PIL fallback.
// Returns malloc'd RGB8 buffer or nullptr; fills width/height.
uint8_t* fc_jpeg_decode(const uint8_t* data, size_t len, int scale_num,
                        int* width, int* height) {
  size_t cap;
  int reused;
  return decode_full(data, len, scale_num, width, height, nullptr, &cap,
                     &reused);
}

// ---------------------------------------------------------------------------
// ROI decode: decode only the source window a crop/extract-dominant plan
// actually consumes (libjpeg-turbo jpeg_crop_scanline + jpeg_skip_scanlines,
// composable with the scale_num DCT prescale above). The thumbnail/cropzoom
// firehose spends most of its decode time on pixels it throws away; this is
// the decode-side twin of the resample's span window.
// ---------------------------------------------------------------------------

// 1 when this build can honor fc_jpeg_decode_roi (libjpeg-turbo >= 1.5
// provides the crop/skip scanline API; plain libjpeg cannot).
int fc_roi_supported() {
#if defined(LIBJPEG_TURBO_VERSION)
  return 1;
#else
  return 0;
#endif
}

// Decode a sub-window of a JPEG to RGB. ``scale_num`` as in
// fc_jpeg_decode; ``rx/ry/rw/rh`` are the requested window in OUTPUT
// (post-prescale) coordinates. The decoded window may start left of and
// be wider than requested: jpeg_crop_scanline aligns the left edge down
// to an iMCU boundary and widens the span, so callers MUST consume the
// actualized geometry reported back:
//   width/height  — decoded window dims (the returned buffer's shape)
//   out_x/out_y   — actual window origin in output coordinates
//   full_w/full_h — the full scaled frame dims (what a windowless decode
//                   of this source at this scale would have produced)
// Rows above the window are entropy-skipped (no IDCT); rows below are
// never read (jpeg_abort_decompress). CMYK/YCCK sources fold to RGB like
// fc_jpeg_decode. Returns nullptr on any decode error or when the build
// lacks the turbo API.
uint8_t* fc_jpeg_decode_roi(const uint8_t* data, size_t len, int scale_num,
                            int rx, int ry, int rw, int rh,
                            int* width, int* height, int* out_x, int* out_y,
                            int* full_w, int* full_h) {
#if !defined(LIBJPEG_TURBO_VERSION)
  (void)data; (void)len; (void)scale_num; (void)rx; (void)ry; (void)rw;
  (void)rh; (void)width; (void)height; (void)out_x; (void)out_y;
  (void)full_w; (void)full_h;
  return nullptr;
#else
  jpeg_decompress_struct cinfo;
  fc_jpeg_error_mgr jerr;
  cinfo.err = jpeg_std_error(&jerr.pub);
  jerr.pub.error_exit = fc_jpeg_error_exit;
  // volatile across the setjmp boundary, same reasoning as fc_jpeg_decode
  uint8_t* volatile out = nullptr;
  uint8_t* volatile row4 = nullptr;  // CMYK scanline scratch
  if (setjmp(jerr.setjmp_buffer)) {
    // error path for malformed/truncated bytes: abort + destroy releases
    // every libjpeg allocation, and the worker thread running this task
    // (fc_pool) returns to its loop untouched — pool abort safety is
    // exactly this function never leaking or crashing on hostile input
    jpeg_destroy_decompress(&cinfo);
    std::free(out);
    std::free(row4);
    return nullptr;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, data, len);
  if (jpeg_read_header(&cinfo, TRUE) != JPEG_HEADER_OK) {
    jpeg_destroy_decompress(&cinfo);
    return nullptr;
  }
  const bool cmyk = cinfo.jpeg_color_space == JCS_CMYK ||
                    cinfo.jpeg_color_space == JCS_YCCK;
  cinfo.out_color_space = cmyk ? JCS_CMYK : JCS_RGB;
  const bool inverted = cinfo.saw_Adobe_marker ||
                        cinfo.jpeg_color_space == JCS_YCCK;
  if (scale_num >= 1 && scale_num <= 8) {
    cinfo.scale_num = scale_num;
    cinfo.scale_denom = 8;
  }
  cinfo.do_fancy_upsampling = TRUE;
  jpeg_start_decompress(&cinfo);
  const int fw = static_cast<int>(cinfo.output_width);
  const int fh = static_cast<int>(cinfo.output_height);
  // clamp the requested window to the scaled frame (degenerate -> error)
  if (rx < 0) { rw += rx; rx = 0; }
  if (ry < 0) { rh += ry; ry = 0; }
  if (rx + rw > fw) rw = fw - rx;
  if (ry + rh > fh) rh = fh - ry;
  if (rw <= 0 || rh <= 0 || rx >= fw || ry >= fh) {
    jpeg_abort_decompress(&cinfo);
    jpeg_destroy_decompress(&cinfo);
    return nullptr;
  }
  JDIMENSION xoff = static_cast<JDIMENSION>(rx);
  JDIMENSION xw = static_cast<JDIMENSION>(rw);
  if (xoff != 0 || xw != cinfo.output_width) {
    // aligns xoff down to the (scaled) iMCU boundary and widens xw; a
    // full-width request skips the call (crop_scanline rejects it)
    jpeg_crop_scanline(&cinfo, &xoff, &xw);
  }
  const int w = static_cast<int>(xw);
  const int stride = w * 3;
  out = static_cast<uint8_t*>(
      std::malloc(static_cast<size_t>(stride) * rh));
  if (!out) {
    jpeg_abort_decompress(&cinfo);
    jpeg_destroy_decompress(&cinfo);
    return nullptr;
  }
  if (cmyk) {
    row4 = static_cast<uint8_t*>(std::malloc(static_cast<size_t>(w) * 4));
    if (!row4) {
      jpeg_abort_decompress(&cinfo);
      jpeg_destroy_decompress(&cinfo);
      std::free(out);
      return nullptr;
    }
  }
  if (ry > 0) {
    jpeg_skip_scanlines(&cinfo, static_cast<JDIMENSION>(ry));
  }
  int written = 0;
  while (written < rh && cinfo.output_scanline < cinfo.output_height) {
    uint8_t* row = out + static_cast<size_t>(written) * stride;
    if (!cmyk) {
      JSAMPROW rows[1] = {row};
      written += static_cast<int>(jpeg_read_scanlines(&cinfo, rows, 1));
      continue;
    }
    JSAMPROW rows[1] = {row4};
    if (jpeg_read_scanlines(&cinfo, rows, 1) != 1) break;
    for (int x = 0; x < w; ++x) {
      const int c = row4[x * 4 + 0], m = row4[x * 4 + 1];
      const int y = row4[x * 4 + 2], k = row4[x * 4 + 3];
      if (inverted) {
        row[x * 3 + 0] = static_cast<uint8_t>(c * k / 255);
        row[x * 3 + 1] = static_cast<uint8_t>(m * k / 255);
        row[x * 3 + 2] = static_cast<uint8_t>(y * k / 255);
      } else {
        row[x * 3 + 0] = static_cast<uint8_t>((255 - c) * (255 - k) / 255);
        row[x * 3 + 1] = static_cast<uint8_t>((255 - m) * (255 - k) / 255);
        row[x * 3 + 2] = static_cast<uint8_t>((255 - y) * (255 - k) / 255);
      }
    }
    ++written;
  }
  std::free(row4);
  row4 = nullptr;
  if (written < rh) {
    // truncated stream inside the window: the buffer is partial — fail
    // rather than hand back rows of uninitialized memory
    jpeg_abort_decompress(&cinfo);
    jpeg_destroy_decompress(&cinfo);
    std::free(out);
    return nullptr;
  }
  // the tail below the window is never needed: abort skips its entropy
  // decode entirely (finish_decompress would insist on consuming it)
  jpeg_abort_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  *width = w;
  *height = rh;
  *out_x = static_cast<int>(xoff);
  *out_y = ry;
  *full_w = fw;
  *full_h = fh;
  return out;
#endif
}

// Luma sampling factors must satisfy the JPEG MCU budget (sum of h*v over
// components <= 10; chroma is always 1x1 here, so luma h*v <= 8) and
// libjpeg's 1..4 range. ImageMagick enforces the same constraints on its
// -sampling-factor geometry.
static bool fc_samp_valid(int samp_h, int samp_v) {
  return samp_h >= 1 && samp_h <= 4 && samp_v >= 1 && samp_v <= 4 &&
         samp_h * samp_v <= 8;
}

// Encode RGB8 to JPEG. quality 0..100; optimize!=0 enables optimized Huffman
// tables; progressive!=0 enables the progressive scan script; samp_h/samp_v
// are the LUMA sampling factors (chroma stays 1x1), the IM -sampling-factor
// "HxV" geometry: 1x1 = 4:4:4 (the reference's default,
// config/parameters.yml:102), 2x2 = 4:2:0, 2x1 = 4:2:2, 1x2 = 4:4:0.
uint8_t* fc_jpeg_encode(const uint8_t* rgb, int width, int height, int quality,
                        int optimize, int progressive, int samp_h, int samp_v,
                        size_t* out_len) {
  if (!fc_samp_valid(samp_h, samp_v)) return nullptr;
  jpeg_compress_struct cinfo;
  fc_jpeg_error_mgr jerr;
  cinfo.err = jpeg_std_error(&jerr.pub);
  jerr.pub.error_exit = fc_jpeg_error_exit;
  unsigned char* mem = nullptr;
  unsigned long mem_len = 0;
  if (setjmp(jerr.setjmp_buffer)) {
    jpeg_destroy_compress(&cinfo);
    std::free(mem);
    return nullptr;
  }
  jpeg_create_compress(&cinfo);
  jpeg_mem_dest(&cinfo, &mem, &mem_len);
  cinfo.image_width = width;
  cinfo.image_height = height;
  cinfo.input_components = 3;
  cinfo.in_color_space = JCS_RGB;
  jpeg_set_defaults(&cinfo);
  jpeg_set_quality(&cinfo, quality, TRUE);
  cinfo.optimize_coding = optimize ? TRUE : FALSE;
  if (progressive) jpeg_simple_progression(&cinfo);
  for (int i = 0; i < cinfo.num_components; ++i) {
    cinfo.comp_info[i].h_samp_factor = (i == 0) ? samp_h : 1;
    cinfo.comp_info[i].v_samp_factor = (i == 0) ? samp_v : 1;
  }
  jpeg_start_compress(&cinfo, TRUE);
  const int stride = width * 3;
  while (cinfo.next_scanline < cinfo.image_height) {
    const uint8_t* row = rgb + static_cast<size_t>(cinfo.next_scanline) * stride;
    JSAMPROW rows[1] = {const_cast<uint8_t*>(row)};
    jpeg_write_scanlines(&cinfo, rows, 1);
  }
  jpeg_finish_compress(&cinfo);
  jpeg_destroy_compress(&cinfo);
  *out_len = mem_len;
  // hand back a malloc'd copy so fc_free() semantics are uniform
  uint8_t* out = static_cast<uint8_t*>(std::malloc(mem_len));
  if (out) std::memcpy(out, mem, mem_len);
  std::free(mem);
  return out;
}

// ---------------------------------------------------------------------------
// MozJPEG-grade encode: trellis-quantized coefficients.
//
// cjpeg's size edge over vanilla libjpeg comes from three techniques:
// optimized Huffman tables, a progressive scan script (both above), and
// trellis quantization — rate-distortion-optimal coefficient rounding
// (Crouse & Ramchandran '97), which vanilla libjpeg cannot do because its
// API never exposes the coefficients. Here we compute the DCT ourselves
// (orthonormal 8x8, so coefficient-domain SSE == pixel-domain SSE by
// Parseval), run the trellis DP per block against a Huffman-bit rate
// model, and hand the chosen coefficients to libjpeg via
// jpeg_write_coefficients for entropy coding with optimized tables.
// ---------------------------------------------------------------------------

namespace trellis {

// zigzag position -> natural (row-major) index
static const int kZigzagToNat[64] = {
    0,  1,  8,  16, 9,  2,  3,  10, 17, 24, 32, 25, 18, 11, 4,  5,
    12, 19, 26, 33, 40, 48, 41, 34, 27, 20, 13, 6,  7,  14, 21, 28,
    35, 42, 49, 56, 57, 50, 43, 36, 29, 22, 15, 23, 30, 37, 44, 51,
    58, 59, 52, 45, 38, 31, 39, 46, 53, 60, 61, 54, 47, 55, 62, 63};

// Annex K base tables (natural order)
static const int kLumaQ[64] = {
    16, 11, 10, 16, 24,  40,  51,  61,  12, 12, 14, 19, 26,  58,  60,  55,
    14, 13, 16, 24, 40,  57,  69,  56,  14, 17, 22, 29, 51,  87,  80,  62,
    18, 22, 37, 56, 68,  109, 103, 77,  24, 35, 55, 64, 81,  104, 113, 92,
    49, 64, 78, 87, 103, 121, 120, 101, 72, 92, 95, 98, 112, 100, 103, 99};
static const int kChromaQ[64] = {
    17, 18, 24, 47, 99, 99, 99, 99, 18, 21, 26, 66, 99, 99, 99, 99,
    24, 26, 56, 99, 99, 99, 99, 99, 47, 66, 99, 99, 99, 99, 99, 99,
    99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99,
    99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99};

// code lengths of the Annex K standard AC Huffman tables, indexed
// [run][size] (size 1..10); used as the rate model for the trellis (the
// final tables are optimized per image, this is the proxy mozjpeg also
// starts from). Values = code bits; total rate = code bits + size bits.
static int ac_code_bits_luma[16][11];
static int ac_code_bits_chroma[16][11];
static int eob_bits_luma, eob_bits_chroma, zrl_bits_luma, zrl_bits_chroma;
static std::once_flag rate_tables_once;

static void init_rate_tables_from(const int* bits, const int* vals,
                                  int table[16][11], int* eob, int* zrl) {
  int lengths[256];
  std::memset(lengths, 0, sizeof(lengths));
  int k = 0;
  for (int len = 1; len <= 16; ++len) {
    for (int i = 0; i < bits[len]; ++i) {
      lengths[vals[k]] = len;
      ++k;
    }
  }
  for (int run = 0; run < 16; ++run) {
    for (int size = 1; size <= 10; ++size) {
      const int sym = (run << 4) | size;
      table[run][size] = lengths[sym] ? lengths[sym] : 24;  // escape-ish
    }
  }
  *eob = lengths[0x00] ? lengths[0x00] : 24;
  *zrl = lengths[0xF0] ? lengths[0xF0] : 24;
}

static void init_rate_tables() {
  // Annex K table K.5 (luma AC) / K.6 (chroma AC): BITS + HUFFVAL
  static const int lb[17] = {0, 0, 2, 1, 3, 3, 2, 4, 3, 5, 5, 4, 4, 0, 0, 1, 0x7d};
  static const int lv[162] = {
      0x01, 0x02, 0x03, 0x00, 0x04, 0x11, 0x05, 0x12, 0x21, 0x31, 0x41, 0x06,
      0x13, 0x51, 0x61, 0x07, 0x22, 0x71, 0x14, 0x32, 0x81, 0x91, 0xa1, 0x08,
      0x23, 0x42, 0xb1, 0xc1, 0x15, 0x52, 0xd1, 0xf0, 0x24, 0x33, 0x62, 0x72,
      0x82, 0x09, 0x0a, 0x16, 0x17, 0x18, 0x19, 0x1a, 0x25, 0x26, 0x27, 0x28,
      0x29, 0x2a, 0x34, 0x35, 0x36, 0x37, 0x38, 0x39, 0x3a, 0x43, 0x44, 0x45,
      0x46, 0x47, 0x48, 0x49, 0x4a, 0x53, 0x54, 0x55, 0x56, 0x57, 0x58, 0x59,
      0x5a, 0x63, 0x64, 0x65, 0x66, 0x67, 0x68, 0x69, 0x6a, 0x73, 0x74, 0x75,
      0x76, 0x77, 0x78, 0x79, 0x7a, 0x83, 0x84, 0x85, 0x86, 0x87, 0x88, 0x89,
      0x8a, 0x92, 0x93, 0x94, 0x95, 0x96, 0x97, 0x98, 0x99, 0x9a, 0xa2, 0xa3,
      0xa4, 0xa5, 0xa6, 0xa7, 0xa8, 0xa9, 0xaa, 0xb2, 0xb3, 0xb4, 0xb5, 0xb6,
      0xb7, 0xb8, 0xb9, 0xba, 0xc2, 0xc3, 0xc4, 0xc5, 0xc6, 0xc7, 0xc8, 0xc9,
      0xca, 0xd2, 0xd3, 0xd4, 0xd5, 0xd6, 0xd7, 0xd8, 0xd9, 0xda, 0xe1, 0xe2,
      0xe3, 0xe4, 0xe5, 0xe6, 0xe7, 0xe8, 0xe9, 0xea, 0xf1, 0xf2, 0xf3, 0xf4,
      0xf5, 0xf6, 0xf7, 0xf8, 0xf9, 0xfa};
  static const int cb[17] = {0, 0, 2, 1, 2, 4, 4, 3, 4, 7, 5, 4, 4, 0, 1, 2, 0x77};
  static const int cv[162] = {
      0x00, 0x01, 0x02, 0x03, 0x11, 0x04, 0x05, 0x21, 0x31, 0x06, 0x12, 0x41,
      0x51, 0x07, 0x61, 0x71, 0x13, 0x22, 0x32, 0x81, 0x08, 0x14, 0x42, 0x91,
      0xa1, 0xb1, 0xc1, 0x09, 0x23, 0x33, 0x52, 0xf0, 0x15, 0x62, 0x72, 0xd1,
      0x0a, 0x16, 0x24, 0x34, 0xe1, 0x25, 0xf1, 0x17, 0x18, 0x19, 0x1a, 0x26,
      0x27, 0x28, 0x29, 0x2a, 0x35, 0x36, 0x37, 0x38, 0x39, 0x3a, 0x43, 0x44,
      0x45, 0x46, 0x47, 0x48, 0x49, 0x4a, 0x53, 0x54, 0x55, 0x56, 0x57, 0x58,
      0x59, 0x5a, 0x63, 0x64, 0x65, 0x66, 0x67, 0x68, 0x69, 0x6a, 0x73, 0x74,
      0x75, 0x76, 0x77, 0x78, 0x79, 0x7a, 0x82, 0x83, 0x84, 0x85, 0x86, 0x87,
      0x88, 0x89, 0x8a, 0x92, 0x93, 0x94, 0x95, 0x96, 0x97, 0x98, 0x99, 0x9a,
      0xa2, 0xa3, 0xa4, 0xa5, 0xa6, 0xa7, 0xa8, 0xa9, 0xaa, 0xb2, 0xb3, 0xb4,
      0xb5, 0xb6, 0xb7, 0xb8, 0xb9, 0xba, 0xc2, 0xc3, 0xc4, 0xc5, 0xc6, 0xc7,
      0xc8, 0xc9, 0xca, 0xd2, 0xd3, 0xd4, 0xd5, 0xd6, 0xd7, 0xd8, 0xd9, 0xda,
      0xe2, 0xe3, 0xe4, 0xe5, 0xe6, 0xe7, 0xe8, 0xe9, 0xea, 0xf2, 0xf3, 0xf4,
      0xf5, 0xf6, 0xf7, 0xf8, 0xf9, 0xfa};
  init_rate_tables_from(lb, lv, ac_code_bits_luma, &eob_bits_luma,
                        &zrl_bits_luma);
  init_rate_tables_from(cb, cv, ac_code_bits_chroma, &eob_bits_chroma,
                        &zrl_bits_chroma);
}

// concurrent encodes race the lazy init otherwise (served JPEGs would be
// computed from half-written tables); call_once gives the needed fence
static void ensure_rate_tables() { std::call_once(rate_tables_once, init_rate_tables); }

// IJG quality scaling (mirrors jpeg_set_quality + force_baseline)
static void build_qtable(int quality, const int* base, uint16_t q[64]) {
  if (quality < 1) quality = 1;
  if (quality > 100) quality = 100;
  const int scale = quality < 50 ? 5000 / quality : 200 - quality * 2;
  for (int i = 0; i < 64; ++i) {
    int v = (base[i] * scale + 50) / 100;
    if (v < 1) v = 1;
    if (v > 255) v = 255;  // baseline
    q[i] = static_cast<uint16_t>(v);
  }
}

// orthonormal separable 8x8 DCT-II
static float cos_table[8][8];
static std::once_flag cos_once;
static void init_cos() {
  for (int u = 0; u < 8; ++u) {
    const double cu = (u == 0) ? std::sqrt(0.125) : 0.5;
    for (int x = 0; x < 8; ++x) {
      cos_table[u][x] =
          static_cast<float>(cu * std::cos((2 * x + 1) * u * M_PI / 16.0));
    }
  }
}
static void ensure_cos() { std::call_once(cos_once, init_cos); }

static void fdct8x8(const float in[64], float out[64]) {
  float tmp[64];
  for (int y = 0; y < 8; ++y) {       // rows
    for (int u = 0; u < 8; ++u) {
      float s = 0.f;
      for (int x = 0; x < 8; ++x) s += in[y * 8 + x] * cos_table[u][x];
      tmp[y * 8 + u] = s;
    }
  }
  for (int u = 0; u < 8; ++u) {       // cols
    for (int v = 0; v < 8; ++v) {
      float s = 0.f;
      for (int y = 0; y < 8; ++y) s += tmp[y * 8 + u] * cos_table[v][y];
      out[v * 8 + u] = s;
    }
  }
}

static inline int bit_size(int v) {
  int size = 0;
  while (v) {
    ++size;
    v >>= 1;
  }
  return size;
}

// Per-component rate model with lambda pre-multiplied, transposed to
// [size][run] so the DP's inner loop reads one contiguous row.
// lrate[size][m] = lambda * (huffman code bits for (m, size) + size bits).
struct LambdaRates {
  float lrate[11][16];
  float lzrl;   // lambda * ZRL code bits
  float leob;   // lambda * EOB code bits
};

static void build_lambda_rates(float lambda, const int table[16][11],
                               int eob_bits, int zrl_bits, LambdaRates* out) {
  for (int size = 1; size <= 10; ++size) {
    for (int m = 0; m < 16; ++m) {
      out->lrate[size][m] = lambda * (table[m][size] + size);
    }
  }
  out->lzrl = lambda * zrl_bits;
  out->leob = lambda * eob_bits;
}

// Trellis-quantize one block's AC coefficients (zigzag order input) against
// quant values qz (zigzag order). Writes quantized signed values (zigzag
// order) into outz[1..63].
//
// EXACT dynamic program in O(63 * 16): a predecessor at distance
// run = m + 16z (m in 0..15, z ZRL escapes) costs
//     g[j] + z*lzrl + lrate[size][m] + d + prefix[k]
// where g[j] = best[j] - prefix[j+1] folds the "zeros between" term.
// The minimum over z for every residue is carried incrementally in
//     w[i] = min(g[i], w[i-16] + lzrl)
// so each candidate value scans only the 16 run residues — no windowed
// approximation (the previous implementation capped runs at ~34, giving
// up optimality on sparse blocks), and ~5x fewer inner iterations on
// dense blocks, which dominate encode time.
static void trellis_ac(const float* cz, const uint16_t* qz,
                       const LambdaRates& lr, int16_t* outz) {
  float best[64];            // best cost of a path whose LAST nonzero is k
  int prev_nz[64];           // backpointer
  int chosen[64];            // chosen |value| at k
  float prefix[65];          // prefix sums of zero-distortion over 1..63
  float w[64];               // ZRL-folded running min of g by residue
  int wj[64];                // argmin backpointer for w
  prefix[1] = 0.f;
  for (int k = 1; k < 64; ++k) {
    prefix[k + 1] = prefix[k] + cz[k] * cz[k];
  }
  // position 0 = virtual block start: base cost 0, prefix[1] = 0
  w[0] = 0.f;
  wj[0] = 0;
  for (int k = 1; k < 64; ++k) {
    best[k] = 1e30f;
    prev_nz[k] = 0;
    chosen[k] = 0;
    const float a = std::fabs(cz[k]);
    const float q = qz[k];
    int v0 = static_cast<int>(a / q + 0.5f);
    if (v0 > 1023) v0 = 1023;
    if (v0 >= 1) {
      const int mmax = (k - 1 < 15) ? k - 1 : 15;
      for (int dv = 0; dv <= 1; ++dv) {
        const int v = v0 - dv;
        if (v < 1) break;
        const int size = bit_size(v);
        if (size > 10) continue;
        const float fixed =
            (a - v * q) * (a - v * q) + prefix[k];  // d + zeros before k
        const float* rates = lr.lrate[size];
        float bc = w[k - 1] + rates[0];
        int bm = 0;
        for (int m = 1; m <= mmax; ++m) {
          const float c = w[k - 1 - m] + rates[m];
          if (c < bc) {
            bc = c;
            bm = m;
          }
        }
        const float cost = fixed + bc;
        if (cost < best[k]) {
          best[k] = cost;
          prev_nz[k] = wj[k - 1 - bm];
          chosen[k] = v;
        }
      }
    }
    const float g = (best[k] < 1e29f) ? best[k] - prefix[k + 1] : 1e30f;
    if (k >= 16 && w[k - 16] + lr.lzrl < g) {
      w[k] = w[k - 16] + lr.lzrl;
      wj[k] = wj[k - 16];
    } else {
      w[k] = g;
      wj[k] = k;
    }
  }
  // choose the best last-nonzero position (or the all-zero block)
  float total_best = prefix[64] + lr.leob;  // all zero -> EOB only
  int last = 0;
  for (int k = 1; k < 64; ++k) {
    if (best[k] >= 1e29f) continue;
    const float tail = prefix[64] - prefix[k + 1];
    const float cost = best[k] + tail + (k < 63 ? lr.leob : 0.f);
    if (cost < total_best) {
      total_best = cost;
      last = k;
    }
  }
  for (int k = 1; k < 64; ++k) outz[k] = 0;
  for (int k = last; k > 0; k = prev_nz[k]) {
    outz[k] = static_cast<int16_t>(cz[k] < 0 ? -chosen[k] : chosen[k]);
  }
}

}  // namespace trellis

// Encode RGB8 to JPEG with trellis quantization + optimized Huffman +
// progressive scans — the full MozJPEG technique set. samp_h/samp_v are
// the LUMA sampling factors (chroma 1x1), the IM -sampling-factor "HxV"
// geometry: 1x1 = 4:4:4, 2x2 = 4:2:0, 2x1 = 4:2:2, 1x2 = 4:4:0.
uint8_t* fc_jpeg_encode_trellis(const uint8_t* rgb, int width, int height,
                                int quality, int samp_h, int samp_v,
                                int progressive, size_t* out_len) {
  using namespace trellis;
  if (!fc_samp_valid(samp_h, samp_v)) return nullptr;
  ensure_rate_tables();
  ensure_cos();

  const int sub_h = samp_h, sub_v = samp_v;
  const bool subsampled = sub_h > 1 || sub_v > 1;
  const int comp_w[3] = {width, (width + sub_h - 1) / sub_h,
                         (width + sub_h - 1) / sub_h};
  const int comp_h[3] = {height, (height + sub_v - 1) / sub_v,
                         (height + sub_v - 1) / sub_v};

  // RGB -> YCbCr planes (JFIF), chroma box-downsampled for 4:2:0
  std::vector<std::vector<float>> planes(3);
  for (int c = 0; c < 3; ++c) {
    planes[c].resize(static_cast<size_t>(comp_w[c]) * comp_h[c]);
  }
  {
    std::vector<float> cb_full, cr_full;
    if (subsampled) {
      cb_full.resize(static_cast<size_t>(width) * height);
      cr_full.resize(static_cast<size_t>(width) * height);
    }
    for (int y = 0; y < height; ++y) {
      for (int x = 0; x < width; ++x) {
        const uint8_t* p = rgb + (static_cast<size_t>(y) * width + x) * 3;
        const float r = p[0], g = p[1], b = p[2];
        const float yv = 0.299f * r + 0.587f * g + 0.114f * b;
        const float cbv = -0.168735892f * r - 0.331264108f * g + 0.5f * b + 128.f;
        const float crv = 0.5f * r - 0.418687589f * g - 0.081312411f * b + 128.f;
        planes[0][static_cast<size_t>(y) * width + x] = yv;
        if (subsampled) {
          cb_full[static_cast<size_t>(y) * width + x] = cbv;
          cr_full[static_cast<size_t>(y) * width + x] = crv;
        } else {
          planes[1][static_cast<size_t>(y) * width + x] = cbv;
          planes[2][static_cast<size_t>(y) * width + x] = crv;
        }
      }
    }
    if (subsampled) {
      // box-downsample chroma by sub_h x sub_v (edge cells average only
      // the in-bounds samples)
      for (int c = 0; c < 2; ++c) {
        const std::vector<float>& full = c == 0 ? cb_full : cr_full;
        std::vector<float>& out = planes[c + 1];
        for (int y = 0; y < comp_h[1]; ++y) {
          for (int x = 0; x < comp_w[1]; ++x) {
            float acc = 0.f;
            int cnt = 0;
            for (int dy = 0; dy < sub_v; ++dy) {
              for (int dx = 0; dx < sub_h; ++dx) {
                const int sy = y * sub_v + dy, sx = x * sub_h + dx;
                if (sy < height && sx < width) {
                  acc += full[static_cast<size_t>(sy) * width + sx];
                  ++cnt;
                }
              }
            }
            out[static_cast<size_t>(y) * comp_w[1] + x] = acc / cnt;
          }
        }
      }
    }
  }

  uint16_t qt_nat[2][64];
  build_qtable(quality, kLumaQ, qt_nat[0]);
  build_qtable(quality, kChromaQ, qt_nat[1]);
  uint16_t qt_zig[2][64];
  float mean_q_ac[2];
  for (int t = 0; t < 2; ++t) {
    float acc = 0.f;
    for (int k = 0; k < 64; ++k) {
      qt_zig[t][k] = qt_nat[t][kZigzagToNat[k]];
      if (k > 0) acc += qt_zig[t][k];
    }
    mean_q_ac[t] = acc / 63.f;
  }
  // bits->distortion exchange rate; tuned on photographic content for the
  // best bytes-at-PSNR against the plain optimized encoder (overridable
  // for experiments via FC_TRELLIS_LAMBDA)
  float alpha = 0.015f;
  if (const char* env = std::getenv("FC_TRELLIS_LAMBDA")) {
    alpha = std::strtof(env, nullptr);
  }
  const float lambda[2] = {alpha * mean_q_ac[0] * mean_q_ac[0],
                           alpha * mean_q_ac[1] * mean_q_ac[1]};
  LambdaRates lrates[2];
  build_lambda_rates(lambda[0], ac_code_bits_luma, eob_bits_luma,
                     zrl_bits_luma, &lrates[0]);
  build_lambda_rates(lambda[1], ac_code_bits_chroma, eob_bits_chroma,
                     zrl_bits_chroma, &lrates[1]);

  jpeg_compress_struct cinfo;
  fc_jpeg_error_mgr jerr;
  cinfo.err = jpeg_std_error(&jerr.pub);
  jerr.pub.error_exit = fc_jpeg_error_exit;
  unsigned char* mem = nullptr;
  unsigned long mem_len = 0;
  if (setjmp(jerr.setjmp_buffer)) {
    jpeg_destroy_compress(&cinfo);
    std::free(mem);
    return nullptr;
  }
  jpeg_create_compress(&cinfo);
  jpeg_mem_dest(&cinfo, &mem, &mem_len);
  cinfo.image_width = width;
  cinfo.image_height = height;
  cinfo.input_components = 3;
  cinfo.in_color_space = JCS_RGB;
  jpeg_set_defaults(&cinfo);
  jpeg_set_quality(&cinfo, quality, TRUE);
  cinfo.optimize_coding = TRUE;
  if (progressive) jpeg_simple_progression(&cinfo);
  for (int c = 0; c < 3; ++c) {
    cinfo.comp_info[c].h_samp_factor = (c == 0) ? sub_h : 1;
    cinfo.comp_info[c].v_samp_factor = (c == 0) ? sub_v : 1;
  }

  jvirt_barray_ptr coef_arrays[3];
  const int mcu_span_x = 8 * sub_h;  // luma MCU span in samples
  const int mcu_span_y = 8 * sub_v;
  for (int c = 0; c < 3; ++c) {
    const int bw = (comp_w[c] + 7) / 8;
    const int bh = (comp_h[c] + 7) / 8;
    // round block dims up to the MCU grid like libjpeg expects
    const int ch = (c == 0) ? sub_h : 1;
    const int cv = (c == 0) ? sub_v : 1;
    const int mcus_x = (width + mcu_span_x - 1) / mcu_span_x;
    const int mcus_y = (height + mcu_span_y - 1) / mcu_span_y;
    const int full_bw = mcus_x * ch;
    const int full_bh = mcus_y * cv;
    coef_arrays[c] = (*cinfo.mem->request_virt_barray)(
        reinterpret_cast<j_common_ptr>(&cinfo), JPOOL_IMAGE, TRUE,
        static_cast<JDIMENSION>(full_bw > bw ? full_bw : bw),
        static_cast<JDIMENSION>(full_bh > bh ? full_bh : bh),
        static_cast<JDIMENSION>(cv));
  }
  jpeg_write_coefficients(&cinfo, coef_arrays);

  for (int c = 0; c < 3; ++c) {
    const int t = (c == 0) ? 0 : 1;
    const int pw = comp_w[c], ph = comp_h[c];
    const JDIMENSION full_bh = cinfo.comp_info[c].height_in_blocks;
    const JDIMENSION full_bw = cinfo.comp_info[c].width_in_blocks;
    const int table_sel = t;
    for (JDIMENSION brow = 0; brow < full_bh; ++brow) {
      JBLOCKARRAY rows = (*cinfo.mem->access_virt_barray)(
          reinterpret_cast<j_common_ptr>(&cinfo), coef_arrays[c], brow, 1,
          TRUE);
      for (JDIMENSION bcol = 0; bcol < full_bw; ++bcol) {
        float samples[64];
        for (int yy = 0; yy < 8; ++yy) {
          int sy = static_cast<int>(brow) * 8 + yy;
          if (sy >= ph) sy = ph - 1;  // edge replicate
          for (int xx = 0; xx < 8; ++xx) {
            int sx = static_cast<int>(bcol) * 8 + xx;
            if (sx >= pw) sx = pw - 1;
            samples[yy * 8 + xx] =
                planes[c][static_cast<size_t>(sy) * pw + sx] - 128.f;
          }
        }
        float dct_nat[64];
        fdct8x8(samples, dct_nat);
        float cz[64];
        for (int k = 0; k < 64; ++k) cz[k] = dct_nat[kZigzagToNat[k]];

        int16_t outz[64];
        // DC: plain rounding (trellis gains live in the AC runs)
        const float dc = cz[0] / qt_zig[t][0];
        outz[0] = static_cast<int16_t>(dc < 0 ? dc - 0.5f : dc + 0.5f);
        trellis_ac(cz, qt_zig[t], lrates[table_sel], outz);

        JCOEFPTR block = rows[0][bcol];
        std::memset(block, 0, sizeof(JCOEF) * 64);
        for (int k = 0; k < 64; ++k) {
          block[kZigzagToNat[k]] = outz[k];
        }
      }
    }
  }

  jpeg_finish_compress(&cinfo);
  jpeg_destroy_compress(&cinfo);
  *out_len = mem_len;
  uint8_t* out = static_cast<uint8_t*>(std::malloc(mem_len));
  if (out) std::memcpy(out, mem, mem_len);
  std::free(mem);
  return out;
}

// ---------------------------------------------------------------------------
// PNG at 8 bits (libpng 1.6 simplified API)
// ---------------------------------------------------------------------------

// Decode PNG to 8-bit RGB or RGBA (a 16-bit file too: fc_png_decode16
// keeps its depth). channels: pass 3 or 4 to force, or 0 to
// auto-detect (4 iff the file has alpha). Returns malloc'd buffer.
uint8_t* fc_png_decode(const uint8_t* data, size_t len, int want_channels,
                       int* width, int* height, int* channels) {
  png_image image;
  std::memset(&image, 0, sizeof(image));
  image.version = PNG_IMAGE_VERSION;
  if (!png_image_begin_read_from_memory(&image, data, len)) return nullptr;
  int ch = want_channels;
  if (ch == 0) {
    ch = (image.format & PNG_FORMAT_FLAG_ALPHA) ? 4 : 3;
  }
  image.format = (ch == 4) ? PNG_FORMAT_RGBA : PNG_FORMAT_RGB;
  const size_t stride = static_cast<size_t>(image.width) * ch;
  uint8_t* out = static_cast<uint8_t*>(std::malloc(stride * image.height));
  if (!out) {
    png_image_free(&image);
    return nullptr;
  }
  if (!png_image_finish_read(&image, nullptr, out, static_cast<png_int_32>(stride),
                             nullptr)) {
    std::free(out);
    png_image_free(&image);
    return nullptr;
  }
  *width = static_cast<int>(image.width);
  *height = static_cast<int>(image.height);
  *channels = ch;
  return out;
}

// Encode 8-bit RGB/RGBA to PNG. Returns malloc'd buffer.
uint8_t* fc_png_encode(const uint8_t* pixels, int width, int height,
                       int channels, size_t* out_len) {
  png_image image;
  std::memset(&image, 0, sizeof(image));
  image.version = PNG_IMAGE_VERSION;
  image.width = static_cast<png_uint_32>(width);
  image.height = static_cast<png_uint_32>(height);
  image.format = (channels == 4) ? PNG_FORMAT_RGBA : PNG_FORMAT_RGB;
  const png_int_32 stride = width * channels;
  // first pass: measure
  png_alloc_size_t size = 0;
  if (!png_image_write_to_memory(&image, nullptr, &size, 0, pixels, stride,
                                 nullptr)) {
    return nullptr;
  }
  uint8_t* out = static_cast<uint8_t*>(std::malloc(size));
  if (!out) return nullptr;
  if (!png_image_write_to_memory(&image, out, &size, 0, pixels, stride,
                                 nullptr)) {
    std::free(out);
    return nullptr;
  }
  *out_len = size;
  return out;
}

// ---------------------------------------------------------------------------
// PNG at 16 bits. The simplified API reads 16-bit samples only as
// PNG_FORMAT_FLAG_LINEAR, which converts them to linear light; these hand
// over the stored samples, whatever gAMA / sRGB / iCCP chunks the file
// carries. Samples are native endian in memory.
//
// A non-interlaced RGB or RGBA file, the form a master is kept in, is read
// here without libpng: its IDAT stream is inflated whole by libdeflate
// (about twice zlib's speed on a master's grainy, barely compressible
// data) into the frame's own buffer and unfiltered there in place. Grey,
// grey with alpha and interlaced files take libpng's classic API with no
// transform. The encoder writes its rows with libpng's filter choice and
// deflates them whole by libdeflate at the given level.
// ---------------------------------------------------------------------------

static uint32_t fc_be32(const uint8_t* p) {
  return (uint32_t{p[0]} << 24) | (uint32_t{p[1]} << 16) |
         (uint32_t{p[2]} << 8) | uint32_t{p[3]};
}

static void fc_put_be32(uint8_t* p, uint32_t v) {
  p[0] = static_cast<uint8_t>(v >> 24);
  p[1] = static_cast<uint8_t>(v >> 16);
  p[2] = static_cast<uint8_t>(v >> 8);
  p[3] = static_cast<uint8_t>(v);
}

// Swap the bytes of each 16-bit sample of a row of n bytes, in place.
static void fc_swap16(uint8_t* row, size_t n) {
  for (size_t i = 0; i + 1 < n; i += 2) {
    uint16_t v;
    std::memcpy(&v, row + i, 2);
    v = __builtin_bswap16(v);
    std::memcpy(row + i, &v, 2);
  }
}

// Samples are native endian in memory and big endian in a PNG.
static constexpr bool kLittleEndian =
    __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__;

static int fc_paeth(int a, int b, int c) {
  const int pa = std::abs(b - c);
  const int pb = std::abs(a - c);
  const int pc = std::abs(a + b - 2 * c);
  return (pa <= pb && pa <= pc) ? a : (pb <= pc ? b : c);
}

#if defined(__SSE2__)
extern "C++" {
// Sub, Average and Paeth a pixel of BPP (6 or 8) bytes at a time, as eight
// 16-bit lanes: each pixel depends on the one before it, so the bytes of
// one pixel are the only ones that can be worked on together. A pixel of
// src is read whole before its pixel of dst is written.
// (The bytes of a pixel go through registers as a 4-byte and a 2- or
// 4-byte word: a pixel assembled in memory would be read back across two
// stores, which the CPU cannot forward, at many times the cost.)
template <size_t BPP>
using fc_px_tail = typename std::conditional<BPP == 6, uint16_t, uint32_t>::type;

template <size_t BPP>
static __m128i fc_load_px(const uint8_t* p) {
  uint32_t head;
  fc_px_tail<BPP> tail;
  std::memcpy(&head, p, 4);
  std::memcpy(&tail, p + 4, sizeof(tail));
  const uint64_t v = head | (static_cast<uint64_t>(tail) << 32);
  return _mm_unpacklo_epi8(_mm_cvtsi64_si128(static_cast<long long>(v)),
                           _mm_setzero_si128());
}

template <size_t BPP>
static void fc_store_px(uint8_t* p, __m128i v) {
  const __m128i bytes = _mm_and_si128(v, _mm_set1_epi16(0xff));
  const uint64_t out =
      static_cast<uint64_t>(_mm_cvtsi128_si64(_mm_packus_epi16(bytes, bytes)));
  const uint32_t head = static_cast<uint32_t>(out);
  const fc_px_tail<BPP> tail = static_cast<fc_px_tail<BPP>>(out >> 32);
  std::memcpy(p, &head, 4);
  std::memcpy(p + 4, &tail, sizeof(tail));
}

static __m128i fc_abs16(__m128i v) {
  return _mm_max_epi16(v, _mm_sub_epi16(_mm_setzero_si128(), v));
}

template <size_t BPP>
static void fc_unfilter_px(int ft, const uint8_t* src, uint8_t* dst,
                           const uint8_t* prev, size_t n) {
  __m128i a = _mm_setzero_si128();
  __m128i c = _mm_setzero_si128();
  for (size_t i = 0; i < n; i += BPP) {
    const __m128i d = fc_load_px<BPP>(src + i);
    const __m128i b = fc_load_px<BPP>(prev + i);
    __m128i pred;
    if (ft == 1) {
      pred = a;
    } else if (ft == 3) {
      pred = _mm_srli_epi16(_mm_add_epi16(a, b), 1);
    } else {
      const __m128i pa = fc_abs16(_mm_sub_epi16(b, c));
      const __m128i pb = fc_abs16(_mm_sub_epi16(a, c));
      const __m128i pc =
          fc_abs16(_mm_sub_epi16(_mm_add_epi16(a, b), _mm_add_epi16(c, c)));
      const __m128i least = _mm_min_epi16(pa, _mm_min_epi16(pb, pc));
      const __m128i take_a = _mm_cmpeq_epi16(least, pa);
      const __m128i take_b = _mm_cmpeq_epi16(least, pb);
      const __m128i b_or_c = _mm_or_si128(_mm_and_si128(take_b, b),
                                          _mm_andnot_si128(take_b, c));
      pred = _mm_or_si128(_mm_and_si128(take_a, a),
                          _mm_andnot_si128(take_a, b_or_c));
      c = b;
    }
    a = _mm_and_si128(_mm_add_epi16(d, pred), _mm_set1_epi16(0xff));
    fc_store_px<BPP>(dst + i, a);
  }
}
}  // extern "C++"
#endif

// Reconstruct one row of filter type ft from src into dst (which may start
// below src in the same buffer: every byte is read before it is written
// over), given the reconstructed row above (zeros for the first) and the
// bytes of a pixel. False for a filter type PNG does not define.
static bool fc_unfilter_row(int ft, const uint8_t* src, uint8_t* dst,
                            const uint8_t* prev, size_t n, size_t bpp) {
#if defined(__SSE2__)
  if (ft == 1 || ft == 3 || ft == 4) {
    if (bpp == 6) {
      fc_unfilter_px<6>(ft, src, dst, prev, n);
      return true;
    }
    if (bpp == 8) {
      fc_unfilter_px<8>(ft, src, dst, prev, n);
      return true;
    }
  }
#endif
  switch (ft) {
    case 0:
      std::memmove(dst, src, n);
      return true;
    case 1:
      for (size_t i = 0; i < bpp; ++i) dst[i] = src[i];
      for (size_t i = bpp; i < n; ++i) {
        dst[i] = static_cast<uint8_t>(src[i] + dst[i - bpp]);
      }
      return true;
    case 2:
      for (size_t i = 0; i < n; ++i) {
        dst[i] = static_cast<uint8_t>(src[i] + prev[i]);
      }
      return true;
    case 3:
      for (size_t i = 0; i < bpp; ++i) {
        dst[i] = static_cast<uint8_t>(src[i] + (prev[i] >> 1));
      }
      for (size_t i = bpp; i < n; ++i) {
        dst[i] = static_cast<uint8_t>(src[i] + ((dst[i - bpp] + prev[i]) >> 1));
      }
      return true;
    case 4:
      for (size_t i = 0; i < bpp; ++i) {
        dst[i] = static_cast<uint8_t>(src[i] + prev[i]);
      }
      for (size_t i = bpp; i < n; ++i) {
        dst[i] = static_cast<uint8_t>(
            src[i] + fc_paeth(dst[i - bpp], prev[i], prev[i - bpp]));
      }
      return true;
    default:
      return false;
  }
}

// The stream of a 16-bit, non-interlaced RGB (3) or RGBA (4) PNG without a
// tRNS chunk: its size and where its IDAT chunks lie. Anything else (or a
// chunk whose CRC does not match) leaves ok false.
struct fc_png16_layout {
  bool ok = false;
  uint32_t width = 0, height = 0;
  int channels = 0;
  std::vector<std::pair<const uint8_t*, size_t>> idat;
  size_t idat_bytes = 0;
};

static fc_png16_layout fc_png16_scan(const uint8_t* data, size_t len) {
  static const uint8_t kSig[8] = {137, 80, 78, 71, 13, 10, 26, 10};
  fc_png16_layout out;
  if (len < 8 + 25 || std::memcmp(data, kSig, 8) != 0) return out;
  size_t pos = 8;
  bool ended = false;
  while (!ended && len - pos >= 12) {
    const size_t n = fc_be32(data + pos);
    if (n > 0x7fffffffu || len - pos - 12 < n) return out;
    const uint8_t* type = data + pos + 4;
    const uint8_t* body = type + 4;
    const bool critical = !(type[0] & 0x20);
    if (critical &&
        libdeflate_crc32(0, type, n + 4) != fc_be32(body + n)) {
      return out;
    }
    if (pos == 8) {
      // IHDR first: 16 bits, RGB or RGBA, deflate, filter method 0, no
      // interlace
      if (std::memcmp(type, "IHDR", 4) != 0 || n != 13) return out;
      out.width = fc_be32(body);
      out.height = fc_be32(body + 4);
      const int color = body[9];
      if (body[8] != 16 || (color != 2 && color != 6) || body[10] != 0 ||
          body[11] != 0 || body[12] != 0 || out.width == 0 ||
          out.height == 0 || out.width > 0x7fffffffu ||
          out.height > 0x7fffffffu) {
        return out;
      }
      out.channels = color == 6 ? 4 : 3;
    } else if (std::memcmp(type, "IDAT", 4) == 0) {
      out.idat.emplace_back(body, n);
      out.idat_bytes += n;
    } else if (std::memcmp(type, "tRNS", 4) == 0) {
      return out;  // a colour key: a transparency the samples do not hold
    } else if (std::memcmp(type, "IEND", 4) == 0) {
      ended = true;
    }
    pos += 12 + n;
  }
  out.ok = ended && !out.idat.empty();
  return out;
}

// The layout's samples into a frame buffer (frame_take; *cap as it says),
// or nullptr for a stream that does not inflate to exactly its rows.
static uint16_t* fc_png16_read(const fc_png16_layout& png, fc_frame_pool* frames,
                               size_t* cap) {
  const size_t bpp = static_cast<size_t>(png.channels) * 2;
  const size_t stride = static_cast<size_t>(png.width) * bpp;
  const size_t h = png.height;
  size_t nbytes = 0;
  if (__builtin_mul_overflow(stride + 1, h, &nbytes)) return nullptr;
  // one stream: the chunks' bodies end to end, where there is more than one
  // (a master's stream is about as large as its frame, so its buffer comes
  // from the frame pool too, and goes back when the stream is inflated)
  const uint8_t* stream = png.idat[0].first;
  uint8_t* joined = nullptr;
  size_t joined_cap = 0;
  if (png.idat.size() > 1) {
    int joined_reused = 0;
    joined = frame_take(frames, png.idat_bytes, &joined_cap, &joined_reused);
    if (!joined) return nullptr;
    size_t at = 0;
    for (const auto& part : png.idat) {
      std::memcpy(joined + at, part.first, part.second);
      at += part.second;
    }
    stream = joined;
  }
  int reused = 0;
  // the rows as stored, each behind its filter byte; the frame is unfiltered
  // toward the front of the same buffer
  uint8_t* buf = frame_take(frames, nbytes, cap, &reused);
  libdeflate_decompressor* inflater =
      buf ? libdeflate_alloc_decompressor() : nullptr;
  size_t got = 0;
  bool ok = inflater != nullptr &&
            libdeflate_zlib_decompress(inflater, stream, png.idat_bytes, buf,
                                       nbytes, &got) == LIBDEFLATE_SUCCESS &&
            got == nbytes;
  if (inflater) libdeflate_free_decompressor(inflater);
  if (joined) frame_give(frames, joined, joined_cap);
  if (ok) {
    std::vector<uint8_t> zeros(stride, 0);
    const uint8_t* prev = zeros.data();
    for (size_t y = 0; y < h && ok; ++y) {
      const uint8_t* row = buf + y * (stride + 1);
      uint8_t* dst = buf + y * stride;
      ok = fc_unfilter_row(row[0], row + 1, dst, prev, stride, bpp);
      // the row above is done with: to native order
      if (kLittleEndian && y > 0) fc_swap16(buf + (y - 1) * stride, stride);
      prev = dst;
    }
    if (ok && kLittleEndian) {
      fc_swap16(buf + (h - 1) * stride, stride);
    }
  }
  if (!ok) {
    if (buf) frame_give(frames, buf, *cap);
    *cap = 0;
    return nullptr;
  }
  return reinterpret_cast<uint16_t*>(buf);
}

static void fc_png_error_fn(png_structp png, png_const_charp) {
  longjmp(png_jmpbuf(png), 1);
}

static void fc_png_warning_fn(png_structp, png_const_charp) {}

struct fc_png_source {
  const uint8_t* data;
  size_t len;
  size_t pos;
};

static void fc_png_read_fn(png_structp png, png_bytep out, png_size_t n) {
  auto* src = static_cast<fc_png_source*>(png_get_io_ptr(png));
  if (src->len - src->pos < n) png_error(png, "truncated PNG");
  std::memcpy(out, src->data + src->pos, n);
  src->pos += n;
}

// Decode a 16-bit PNG of grey or RGB, with or without alpha, to tightly
// packed uint16 RGB (channels 3) or RGBA (channels 4): grey is spread to
// three equal channels, nothing else is changed. Returns nullptr for any
// other bit depth, a palette, a tRNS colour key (a transparency the
// samples alone do not hold), or a broken file. The buffer comes from
// frame_take (``frames`` null: malloc), its capacity back in *cap (0:
// fc_free releases it, else fc_pool_release).
uint16_t* fc_png_decode16(const uint8_t* data, size_t len,
                          fc_frame_pool* frames, int* width, int* height,
                          int* channels, size_t* cap) {
  *cap = 0;
  const fc_png16_layout layout = fc_png16_scan(data, len);
  if (layout.ok) {
    uint16_t* pixels = fc_png16_read(layout, frames, cap);
    if (pixels) {
      *width = static_cast<int>(layout.width);
      *height = static_cast<int>(layout.height);
      *channels = layout.channels;
    }
    return pixels;
  }
  png_structp png = png_create_read_struct(
      PNG_LIBPNG_VER_STRING, nullptr, fc_png_error_fn, fc_png_warning_fn);
  if (!png) return nullptr;
  png_infop info = png_create_info_struct(png);
  if (!info) {
    png_destroy_read_struct(&png, nullptr, nullptr);
    return nullptr;
  }
  fc_png_source src{data, len, 0};
  // written after setjmp and read by its error branch: volatile, so a
  // longjmp never finds a stale copy in a register
  uint8_t* volatile out = nullptr;
  volatile size_t out_cap = 0;
  png_bytep* volatile rows = nullptr;
  if (setjmp(png_jmpbuf(png))) {
    if (out) frame_give(frames, out, out_cap);
    std::free(rows);
    png_destroy_read_struct(&png, &info, nullptr);
    return nullptr;
  }
  png_set_read_fn(png, &src, fc_png_read_fn);
  png_read_info(png, info);
  png_uint_32 w = 0, h = 0;
  int depth = 0, color = 0;
  png_get_IHDR(png, info, &w, &h, &depth, &color, nullptr, nullptr, nullptr);
  if (depth != 16 || (color & PNG_COLOR_MASK_PALETTE) ||
      png_get_valid(png, info, PNG_INFO_tRNS)) {
    png_destroy_read_struct(&png, &info, nullptr);
    return nullptr;
  }
  if (!(color & PNG_COLOR_MASK_COLOR)) png_set_gray_to_rgb(png);
#if __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
  png_set_swap(png);
#endif
  png_set_interlace_handling(png);
  png_read_update_info(png, info);
  const int ch = png_get_channels(png, info);
  const size_t stride = png_get_rowbytes(png, info);
  if ((ch != 3 && ch != 4) || stride != static_cast<size_t>(w) * ch * 2) {
    png_destroy_read_struct(&png, &info, nullptr);
    return nullptr;
  }
  size_t taken = 0;
  int reused = 0;
  out = frame_take(frames, stride * h, &taken, &reused);
  out_cap = taken;
  rows = static_cast<png_bytep*>(std::malloc(sizeof(png_bytep) * h));
  if (!out || !rows) png_error(png, "out of memory");
  for (png_uint_32 y = 0; y < h; ++y) rows[y] = out + stride * y;
  png_read_image(png, rows);
  png_read_end(png, nullptr);
  std::free(rows);
  png_destroy_read_struct(&png, &info, nullptr);
  *width = static_cast<int>(w);
  *height = static_cast<int>(h);
  *channels = ch;
  *cap = taken;
  return reinterpret_cast<uint16_t*>(out);
}

// Filter one row as libpng chooses by default at 8 bits and up: of None,
// Sub, Up, Average and Paeth, the one whose bytes, read as signed, sum to
// the least magnitude (the first such in that order). Writes the filter
// byte and the filtered row to out[0..n].
static void fc_filter_row(const uint8_t* cur, const uint8_t* prev, size_t n,
                          size_t bpp, uint8_t* scratch, uint8_t* out) {
  auto cost = [](const uint8_t* row, size_t n) {
    size_t sum = 0;
    for (size_t i = 0; i < n; ++i) {
      sum += row[i] < 128 ? row[i] : 256 - row[i];
    }
    return sum;
  };
  uint8_t* sub = scratch;
  uint8_t* up = scratch + n;
  uint8_t* avg = scratch + 2 * n;
  uint8_t* paeth = scratch + 3 * n;
  for (size_t i = 0; i < bpp; ++i) {
    sub[i] = cur[i];
    up[i] = static_cast<uint8_t>(cur[i] - prev[i]);
    avg[i] = static_cast<uint8_t>(cur[i] - (prev[i] >> 1));
    paeth[i] = up[i];
  }
  // one loop a filter, each free of branches, so that each vectorises
  for (size_t i = bpp; i < n; ++i) {
    sub[i] = static_cast<uint8_t>(cur[i] - cur[i - bpp]);
  }
  for (size_t i = bpp; i < n; ++i) {
    up[i] = static_cast<uint8_t>(cur[i] - prev[i]);
  }
  for (size_t i = bpp; i < n; ++i) {
    avg[i] = static_cast<uint8_t>(cur[i] - ((cur[i - bpp] + prev[i]) >> 1));
  }
  for (size_t i = bpp; i < n; ++i) {
    const int a = cur[i - bpp], b = prev[i], c = prev[i - bpp];
    const int pa = std::abs(b - c), pb = std::abs(a - c);
    const int pc = std::abs(a + b - 2 * c);
    const int pred = (pa <= pb && pa <= pc) ? a : (pb <= pc ? b : c);
    paeth[i] = static_cast<uint8_t>(cur[i] - pred);
  }
  const uint8_t* rows[5] = {cur, sub, up, avg, paeth};
  int best = 0;
  size_t best_cost = cost(cur, n);
  for (int f = 1; f < 5; ++f) {
    const size_t c = cost(rows[f], n);
    if (c < best_cost) {
      best = f;
      best_cost = c;
    }
  }
  out[0] = static_cast<uint8_t>(best);
  std::memcpy(out + 1, rows[best], n);
}

static uint8_t* fc_put_chunk(uint8_t* at, const char* type, const uint8_t* body,
                             size_t n) {
  fc_put_be32(at, static_cast<uint32_t>(n));
  std::memcpy(at + 4, type, 4);
  if (body != at + 8 && n) std::memcpy(at + 8, body, n);
  fc_put_be32(at + 8 + n, static_cast<uint32_t>(libdeflate_crc32(0, at + 4, n + 4)));
  return at + 12 + n;
}

// Encode native-endian uint16 RGB (channels 3) or RGBA (4) as a
// non-interlaced 16-bit PNG of one IDAT chunk, deflated at `level` (0-9).
// Returns malloc'd buffer.
uint8_t* fc_png_encode16(const uint16_t* pixels, int width, int height,
                         int channels, int level, size_t* out_len) {
  if ((channels != 3 && channels != 4) || width <= 0 || height <= 0 ||
      level < 0 || level > 9) {
    return nullptr;
  }
  const size_t bpp = static_cast<size_t>(channels) * 2;
  const size_t stride = static_cast<size_t>(width) * bpp;
  const size_t h = static_cast<size_t>(height);
  size_t raw_bytes = 0;
  if (__builtin_mul_overflow(stride + 1, h, &raw_bytes)) return nullptr;
  uint8_t* raw = static_cast<uint8_t*>(std::malloc(raw_bytes));
  if (!raw) return nullptr;
  // big-endian rows: this one and the one above, and four filtered forms
  std::vector<uint8_t> rows(6 * stride, 0);
  uint8_t* cur = rows.data();
  uint8_t* prev = cur + stride;
  const uint8_t* src = reinterpret_cast<const uint8_t*>(pixels);
  for (size_t y = 0; y < h; ++y) {
    std::memcpy(cur, src + y * stride, stride);
    if (kLittleEndian) fc_swap16(cur, stride);
    fc_filter_row(cur, prev, stride, bpp, rows.data() + 2 * stride,
                  raw + y * (stride + 1));
    std::swap(cur, prev);
  }
  libdeflate_compressor* deflater = libdeflate_alloc_compressor(level);
  if (!deflater) {
    std::free(raw);
    return nullptr;
  }
  const size_t bound = libdeflate_zlib_compress_bound(deflater, raw_bytes);
  // signature, IHDR, the IDAT's head, its body, its CRC, IEND
  uint8_t* out = static_cast<uint8_t*>(std::malloc(8 + 25 + 8 + bound + 4 + 12));
  size_t zlen = 0;
  if (out) {
    zlen = libdeflate_zlib_compress(deflater, raw, raw_bytes, out + 8 + 25 + 8,
                                    bound);
  }
  libdeflate_free_compressor(deflater);
  std::free(raw);
  if (zlen == 0 || zlen > 0x7fffffffu) {
    std::free(out);
    return nullptr;
  }
  static const uint8_t kSig[8] = {137, 80, 78, 71, 13, 10, 26, 10};
  std::memcpy(out, kSig, 8);
  uint8_t ihdr[13];
  fc_put_be32(ihdr, static_cast<uint32_t>(width));
  fc_put_be32(ihdr + 4, static_cast<uint32_t>(height));
  ihdr[8] = 16;
  ihdr[9] = channels == 4 ? 6 : 2;
  ihdr[10] = ihdr[11] = ihdr[12] = 0;
  uint8_t* at = fc_put_chunk(out + 8, "IHDR", ihdr, 13);
  at = fc_put_chunk(at, "IDAT", at + 8, zlen);
  at = fc_put_chunk(at, "IEND", nullptr, 0);
  *out_len = static_cast<size_t>(at - out);
  return out;
}

// ---------------------------------------------------------------------------
// header probe: format + dimensions + bit depth without a full decode —
// the native `identify` equivalent (reference runs
// `/usr/bin/identify` per image, src/Core/Entity/ImageMetaInfo.php:143-166).
// ---------------------------------------------------------------------------

enum fc_format {
  FC_UNKNOWN = 0,
  FC_JPEG = 1,
  FC_PNG = 2,
  FC_GIF = 3,
  FC_WEBP = 4,
  FC_BMP = 5,
  FC_PDF = 6,
  FC_MP4 = 7,
  FC_WEBM = 8,
  FC_AVI = 9,
  FC_MOV = 10,
};

static uint16_t be16(const uint8_t* p) { return (p[0] << 8) | p[1]; }
static uint32_t be32(const uint8_t* p) {
  return (static_cast<uint32_t>(p[0]) << 24) | (p[1] << 16) | (p[2] << 8) | p[3];
}
static uint16_t le16(const uint8_t* p) { return p[0] | (p[1] << 8); }
static uint32_t le24(const uint8_t* p) { return p[0] | (p[1] << 8) | (p[2] << 16); }
static uint32_t le32(const uint8_t* p) {
  return p[0] | (p[1] << 8) | (p[2] << 16) | (static_cast<uint32_t>(p[3]) << 24);
}

// Walk JPEG markers to the SOFn frame header for dims + sample precision.
static void probe_jpeg(const uint8_t* d, size_t n, int* w, int* h, int* depth) {
  size_t i = 2;
  while (i + 9 < n) {
    if (d[i] != 0xFF) {
      ++i;
      continue;
    }
    const uint8_t marker = d[i + 1];
    if (marker == 0xFF) {  // legal fill byte before a marker
      ++i;
      continue;
    }
    if (marker == 0xD8 || marker == 0x01 || (marker >= 0xD0 && marker <= 0xD7)) {
      i += 2;
      continue;
    }
    if (i + 4 > n) return;
    const uint16_t seglen = be16(d + i + 2);
    if (marker >= 0xC0 && marker <= 0xCF && marker != 0xC4 && marker != 0xC8 &&
        marker != 0xCC) {
      if (i + 9 <= n) {
        *depth = d[i + 4];
        *h = be16(d + i + 5);
        *w = be16(d + i + 7);
      }
      return;
    }
    i += 2 + seglen;
  }
}

// Identify format/dims/bit-depth from leading bytes (>= 64 recommended).
// Returns an fc_format code; unknown fields stay 0.
int fc_probe(const uint8_t* d, size_t n, int* width, int* height, int* depth) {
  *width = *height = *depth = 0;
  if (n < 12) return FC_UNKNOWN;
  if (d[0] == 0xFF && d[1] == 0xD8 && d[2] == 0xFF) {
    probe_jpeg(d, n, width, height, depth);
    return FC_JPEG;
  }
  if (std::memcmp(d, "\x89PNG\r\n\x1a\n", 8) == 0) {
    if (n >= 25) {
      *width = static_cast<int>(be32(d + 16));
      *height = static_cast<int>(be32(d + 20));
      *depth = d[24];  // IHDR bit depth
    }
    return FC_PNG;
  }
  if (std::memcmp(d, "GIF87a", 6) == 0 || std::memcmp(d, "GIF89a", 6) == 0) {
    *width = le16(d + 6);
    *height = le16(d + 8);
    if (n >= 11) *depth = ((d[10] >> 4) & 0x7) + 1;  // color resolution bits
    return FC_GIF;
  }
  if (std::memcmp(d, "RIFF", 4) == 0 && n >= 16 &&
      std::memcmp(d + 8, "WEBP", 4) == 0) {
    *depth = 8;
    if (n >= 30) {
      if (std::memcmp(d + 12, "VP8 ", 4) == 0) {
        *width = le16(d + 26) & 0x3FFF;
        *height = le16(d + 28) & 0x3FFF;
      } else if (std::memcmp(d + 12, "VP8L", 4) == 0) {
        const uint32_t bits = le32(d + 21);
        *width = static_cast<int>((bits & 0x3FFF) + 1);
        *height = static_cast<int>(((bits >> 14) & 0x3FFF) + 1);
      } else if (std::memcmp(d + 12, "VP8X", 4) == 0) {
        *width = static_cast<int>(le24(d + 24) + 1);
        *height = static_cast<int>(le24(d + 27) + 1);
      }
    }
    return FC_WEBP;
  }
  if (d[0] == 'B' && d[1] == 'M') {
    if (n >= 30) {
      *width = static_cast<int>(le32(d + 18));
      const int32_t raw_h = static_cast<int32_t>(le32(d + 22));
      *height = raw_h < 0 ? -raw_h : raw_h;
      *depth = le16(d + 28);
    }
    return FC_BMP;
  }
  if (std::memcmp(d, "%PDF-", 5) == 0) return FC_PDF;
  if (n >= 12 && std::memcmp(d + 4, "ftyp", 4) == 0) {
    if (std::memcmp(d + 8, "qt  ", 4) == 0) return FC_MOV;
    return FC_MP4;
  }
  if (std::memcmp(d, "\x1a\x45\xdf\xa3", 4) == 0) return FC_WEBM;
  if (std::memcmp(d, "RIFF", 4) == 0 && std::memcmp(d + 8, "AVI ", 4) == 0) {
    return FC_AVI;
  }
  return FC_UNKNOWN;
}

// ---------------------------------------------------------------------------
// WebP
// ---------------------------------------------------------------------------

// Decode preserving alpha when the file carries it: fills channels with 3
// or 4 and returns tightly packed RGB/RGBA accordingly (cwebp/dwebp parity
// for transparent sources).
uint8_t* fc_webp_decode_auto(const uint8_t* data, size_t len, int* width,
                             int* height, int* channels) {
  WebPBitstreamFeatures feat;
  if (WebPGetFeatures(data, len, &feat) != VP8_STATUS_OK) return nullptr;
  *channels = feat.has_alpha ? 4 : 3;
  return feat.has_alpha ? WebPDecodeRGBA(data, len, width, height)
                        : WebPDecodeRGB(data, len, width, height);
}

// Encode tightly packed RGB (channels=3) or RGBA (channels=4) — one entry
// point like fc_png_encode, alpha selected by the pixel layout.
uint8_t* fc_webp_encode(const uint8_t* pixels, int width, int height,
                        int channels, float quality, int lossless,
                        size_t* out_len) {
  uint8_t* out = nullptr;
  const int stride = width * channels;
  size_t n;
  if (channels == 4) {
    n = lossless
            ? WebPEncodeLosslessRGBA(pixels, width, height, stride, &out)
            : WebPEncodeRGBA(pixels, width, height, stride, quality, &out);
  } else {
    n = lossless
            ? WebPEncodeLosslessRGB(pixels, width, height, stride, &out)
            : WebPEncodeRGB(pixels, width, height, stride, quality, &out);
  }
  if (n == 0) return nullptr;
  *out_len = n;
  return out;  // WebP uses malloc-compatible allocation; fc_free works
}

// ---------------------------------------------------------------------------
// worker pool: parallel decode/encode on the host while Python's GIL is
// released (the ctypes call site releases it automatically).
// ---------------------------------------------------------------------------

// A worker's instants on CLOCK_MONOTONIC (libstdc++'s steady_clock), in
// nanoseconds: the clock Python's time.perf_counter_ns() reads on Linux, so
// a caller can place them inside its own timing of the pool call.
static int64_t monotonic_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

struct fc_pool {
  std::vector<std::thread> workers;
  std::queue<std::function<void()>> tasks;
  std::mutex mu;
  std::condition_variable cv;
  std::atomic<bool> stop{false};
  // outlives the pool while a buffer it handed out is live
  fc_frame_pool* frames = new fc_frame_pool();
};

fc_pool* fc_pool_create(int n_threads) {
  auto* pool = new fc_pool();
  if (n_threads < 1) n_threads = 1;
  for (int i = 0; i < n_threads; ++i) {
    pool->workers.emplace_back([pool] {
      for (;;) {
        std::function<void()> task;
        {
          std::unique_lock<std::mutex> lock(pool->mu);
          const bool woken = pool->cv.wait_for(
              lock, kFrameTrimEvery,
              [pool] { return pool->stop || !pool->tasks.empty(); });
          if (woken) {
            if (pool->stop && pool->tasks.empty()) return;
            task = std::move(pool->tasks.front());
            pool->tasks.pop();
          }
        }
        if (task) {
          task();
        } else {
          // nothing to do for a while: a quiet process gives back
          // the frames it no longer uses
          frame_trim(pool->frames);
        }
      }
    });
  }
  return pool;
}

// Stops the workers and frees every idle frame buffer; the frame pool
// itself goes with the last buffer still live (fc_pool_release).
void fc_pool_destroy(fc_pool* pool) {
  pool->stop = true;
  pool->cv.notify_all();
  for (auto& worker : pool->workers) worker.join();
  fc_frame_pool* fp = pool->frames;
  std::vector<fc_frame_pool::Idle> idle;
  bool last;
  {
    std::lock_guard<std::mutex> lock(fp->mu);
    fp->closed = true;
    idle.swap(fp->idle);
    fp->idle_bytes = 0;
    last = fp->live == 0;
  }
  for (const auto& buffer : idle) std::free(buffer.ptr);
  if (last) delete fp;
  delete pool;
}

// The pool's worker threads.
int fc_pool_workers(fc_pool* pool) {
  return static_cast<int>(pool->workers.size());
}

// The pool's frame buffers, as fc_pool_release takes them: the handle
// stays valid after fc_pool_destroy while a buffer it handed out is live.
fc_frame_pool* fc_pool_frames(fc_pool* pool) { return pool->frames; }

// Give back a decoded frame whose batch item reported frame_cap > 0 (any
// other buffer is fc_free'd). Safe from any thread, before or after
// fc_pool_destroy.
void fc_pool_release(fc_frame_pool* frames, void* ptr, size_t cap) {
  frame_give(frames, static_cast<uint8_t*>(ptr), cap);
}

// Pool full frames of min_bytes and up instead of kFramePoolMinBytes, and
// free idle buffers after idle_ms instead of kFrameIdleMs; 0 and a negative
// idle_ms keep the limit (tests decode small frames through the pool, and
// age them, with it).
void fc_pool_set_frame_limits(fc_pool* pool, size_t min_bytes,
                              int64_t idle_ms) {
  if (min_bytes > 0) pool->frames->min_bytes = min_bytes;
  if (idle_ms >= 0) pool->frames->idle_ms = idle_ms;
}

// The frame pool's state into out[5]: idle buffers, live ones, their
// bytes (idle, live), and the most bytes live at once.
void fc_frame_pool_stats(fc_frame_pool* frames, size_t* out) {
  std::lock_guard<std::mutex> lock(frames->mu);
  out[0] = frames->idle.size();
  out[1] = frames->live;
  out[2] = frames->idle_bytes;
  out[3] = frames->live_bytes;
  out[4] = frames->peak_bytes;
}

struct fc_batch_item {
  const uint8_t* data;
  size_t len;
  int scale_num;
  // requested ROI window in OUTPUT (post-prescale) coordinates;
  // roi_w <= 0 means a full-frame decode. The actualized window geometry
  // comes back in out_x/out_y/full_w/full_h (see fc_jpeg_decode_roi).
  int roi_x;
  int roi_y;
  int roi_w;
  int roi_h;
  uint8_t* out;
  int width;
  int height;
  int out_x;
  int out_y;
  int full_w;
  int full_h;
  // a full frame the pool keeps (see kFramePoolMinBytes): its buffer's
  // capacity, which fc_pool_release takes; 0 for a buffer fc_free takes.
  // frame_reused is 1 where an earlier frame had touched those pages.
  size_t frame_cap;
  int frame_reused;
  // when a worker took the item and when it was done with it
  // (monotonic_ns)
  int64_t t_start_ns;
  int64_t t_end_ns;
};

// Decode a batch of JPEGs in parallel on the pool; blocks until done.
// Items may mix full-frame and ROI decodes (roi_w > 0); a per-item
// failure (malformed/truncated bytes) nulls that item's `out` and the
// worker thread survives — the error path in both decoders is a
// setjmp-contained cleanup, never an abort of the process or the pool.
// A full frame of the frame pool's size is decoded into one of its kept
// buffers (frame_cap > 0: give it back through fc_pool_release).
void fc_pool_decode_jpeg_batch(fc_pool* pool, fc_batch_item* items, int n) {
  frame_trim(pool->frames);
  std::atomic<int> remaining{n};
  std::mutex done_mu;
  std::condition_variable done_cv;
  fc_frame_pool* frames = pool->frames;
  for (int i = 0; i < n; ++i) {
    fc_batch_item* item = &items[i];
    {
      std::lock_guard<std::mutex> lock(pool->mu);
      pool->tasks.emplace([item, frames, &remaining, &done_mu, &done_cv] {
        item->t_start_ns = monotonic_ns();
        item->frame_cap = 0;
        item->frame_reused = 0;
        if (item->roi_w > 0 && item->roi_h > 0) {
          item->out = fc_jpeg_decode_roi(
              item->data, item->len, item->scale_num, item->roi_x,
              item->roi_y, item->roi_w, item->roi_h, &item->width,
              &item->height, &item->out_x, &item->out_y, &item->full_w,
              &item->full_h);
        } else {
          item->out = decode_full(item->data, item->len, item->scale_num,
                                  &item->width, &item->height, frames,
                                  &item->frame_cap, &item->frame_reused);
        }
        item->t_end_ns = monotonic_ns();
        if (remaining.fetch_sub(1) == 1) {
          std::lock_guard<std::mutex> dl(done_mu);
          done_cv.notify_all();
        }
      });
    }
    pool->cv.notify_one();
  }
  std::unique_lock<std::mutex> lock(done_mu);
  done_cv.wait(lock, [&remaining] { return remaining.load() == 0; });
}

struct fc_encode_item {
  const uint8_t* rgb;
  int width;
  int height;
  int quality;
  int trellis;      // 1 = trellis DP (moz path), 0 = plain libjpeg encode
  int optimize;     // plain path only (trellis always optimizes Huffman)
  int progressive;
  int samp_h;       // luma sampling factors (IM -sampling-factor HxV)
  int samp_v;
  uint8_t* out;     // fc_free() when done; null on per-image failure
  size_t out_len;
  int64_t t_start_ns;  // as fc_batch_item's
  int64_t t_end_ns;
};

// Encode a batch of RGB frames to JPEG in parallel on the pool; blocks
// until done. The trellis DP is the expensive half of the miss path
// (SURVEY.md hard part 2: "MozJPEG host encode must be threaded or it
// becomes the serial bottleneck") — this is the encode-side twin of
// fc_pool_decode_jpeg_batch, so a 32-way burst of misses pays ~one
// encode latency, not 32.
void fc_pool_encode_jpeg_batch(fc_pool* pool, fc_encode_item* items, int n) {
  std::atomic<int> remaining{n};
  std::mutex done_mu;
  std::condition_variable done_cv;
  for (int i = 0; i < n; ++i) {
    fc_encode_item* item = &items[i];
    {
      std::lock_guard<std::mutex> lock(pool->mu);
      pool->tasks.emplace([item, &remaining, &done_mu, &done_cv] {
        item->t_start_ns = monotonic_ns();
        item->out_len = 0;
        if (item->trellis) {
          item->out = fc_jpeg_encode_trellis(
              item->rgb, item->width, item->height, item->quality,
              item->samp_h, item->samp_v, item->progressive, &item->out_len);
        } else {
          item->out = fc_jpeg_encode(
              item->rgb, item->width, item->height, item->quality,
              item->optimize, item->progressive, item->samp_h, item->samp_v,
              &item->out_len);
        }
        item->t_end_ns = monotonic_ns();
        if (remaining.fetch_sub(1) == 1) {
          std::lock_guard<std::mutex> dl(done_mu);
          done_cv.notify_all();
        }
      });
    }
    pool->cv.notify_one();
  }
  std::unique_lock<std::mutex> lock(done_mu);
  done_cv.wait(lock, [&remaining] { return remaining.load() == 0; });
}

}  // extern "C"
