"""Build (incrementally, through make) and load libtnn_host.so."""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import Optional

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
# TNN_NATIVE_LIB points at an alternative .so — used to run the suite against
# the sanitizer builds (native/build-debug, native/build-tsan) or an installed
# layout where native/ is not a sibling of the package
_SO_OVERRIDE = os.environ.get("TNN_NATIVE_LIB")
_SO_PATH = _SO_OVERRIDE or os.path.join(_NATIVE_DIR, "build", "libtnn_host.so")


def build_native(force: bool = False) -> str:
    """Compile libtnn_host.so via make. Returns the .so path; raises on failure."""
    if force:
        subprocess.run(["make", "-C", _NATIVE_DIR, "clean"], check=True,
                       capture_output=True)
    res = subprocess.run(["make", "-C", _NATIVE_DIR, "-j"],
                         capture_output=True, text=True)
    if res.returncode != 0:
        raise RuntimeError(f"native build failed:\n{res.stdout}\n{res.stderr}")
    return _SO_PATH


def _configure(lib: ctypes.CDLL) -> None:
    c = ctypes
    i64, i32, u8, u64 = c.c_int64, c.c_int32, c.c_uint8, c.c_uint64
    p = c.POINTER

    lib.tnn_mnist_csv_rows.restype = i64
    lib.tnn_mnist_csv_rows.argtypes = [c.c_char_p, c.c_int]
    lib.tnn_mnist_csv_parse.restype = i64
    lib.tnn_mnist_csv_parse.argtypes = [c.c_char_p, c.c_int, p(u8), p(i32), i64, i64]
    lib.tnn_cifar_records.restype = i64
    lib.tnn_cifar_records.argtypes = [c.c_char_p, c.c_int]
    lib.tnn_cifar10_parse.restype = i64
    lib.tnn_cifar10_parse.argtypes = [c.c_char_p, p(u8), p(i32), i64]
    lib.tnn_cifar100_parse.restype = i64
    lib.tnn_cifar100_parse.argtypes = [c.c_char_p, p(u8), p(i32), p(i32), i64]

    f32 = c.c_float
    lib.tnn_gather_rows_f32.restype = None
    lib.tnn_gather_rows_f32.argtypes = [p(f32), i64, p(i64), i64, p(f32)]
    lib.tnn_gather_rows_u8.restype = None
    lib.tnn_gather_rows_u8.argtypes = [p(u8), i64, p(i64), i64, p(u8)]
    lib.tnn_gather_u8_normalize_f32.restype = None
    lib.tnn_gather_u8_normalize_f32.argtypes = [p(u8), i64, p(i64), i64, p(f32),
                                                p(f32), p(f32), i64]
    lib.tnn_epoch_permutation.restype = None
    lib.tnn_epoch_permutation.argtypes = [i64, u64, p(i64)]

    lib.tnn_bpe_load.restype = c.c_void_p
    lib.tnn_bpe_load.argtypes = [c.c_char_p]
    lib.tnn_bpe_free.restype = None
    lib.tnn_bpe_free.argtypes = [c.c_void_p]
    lib.tnn_bpe_vocab_size.restype = i32
    lib.tnn_bpe_vocab_size.argtypes = [c.c_void_p]
    lib.tnn_bpe_eot.restype = i32
    lib.tnn_bpe_eot.argtypes = [c.c_void_p]
    lib.tnn_bpe_encode.restype = i64
    lib.tnn_bpe_encode.argtypes = [c.c_void_p, c.c_char_p, i64, p(i32), i64]
    lib.tnn_bpe_decode.restype = i64
    lib.tnn_bpe_decode.argtypes = [c.c_void_p, p(i32), i64, c.c_char_p, i64]

    lib.tnn_ctl_create.restype = c.c_void_p
    lib.tnn_ctl_create.argtypes = [c.c_char_p, c.c_int]
    lib.tnn_ctl_port.restype = c.c_int
    lib.tnn_ctl_port.argtypes = [c.c_void_p]
    lib.tnn_ctl_connect.restype = i64
    lib.tnn_ctl_connect.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.tnn_ctl_send.restype = c.c_int
    lib.tnn_ctl_send.argtypes = [c.c_void_p, i64, i32, p(u8), i64]
    lib.tnn_ctl_recv.restype = i64
    lib.tnn_ctl_recv.argtypes = [c.c_void_p, c.c_double, p(i64), p(i32), p(u8), i64]
    lib.tnn_ctl_close_conn.restype = None
    lib.tnn_ctl_close_conn.argtypes = [c.c_void_p, i64]
    lib.tnn_ctl_destroy.restype = None
    lib.tnn_ctl_destroy.argtypes = [c.c_void_p]

    lib.tnn_tokens_open.restype = c.c_void_p
    lib.tnn_tokens_open.argtypes = [c.c_char_p, c.c_int]
    lib.tnn_tokens_len.restype = i64
    lib.tnn_tokens_len.argtypes = [c.c_void_p]
    lib.tnn_tokens_windows.restype = None
    lib.tnn_tokens_windows.argtypes = [c.c_void_p, p(i64), i64, i64, p(i32)]
    lib.tnn_tokens_close.restype = None
    lib.tnn_tokens_close.argtypes = [c.c_void_p]

    lib.tnn_decode_png_batch.restype = i64
    lib.tnn_decode_png_batch.argtypes = [p(c.c_char_p), i64, c.c_int, c.c_int,
                                         p(u8), p(u8)]
    # unified PNG+JPEG entry (declared here so a stale .so without the symbol
    # raises AttributeError and get_lib() falls back, loudly)
    lib.tnn_decode_image_batch.restype = i64
    lib.tnn_decode_image_batch.argtypes = [p(c.c_char_p), i64, c.c_int,
                                           c.c_int, p(u8), p(u8)]
    lib.tnn_resize_bilinear_batch.restype = None
    lib.tnn_resize_bilinear_batch.argtypes = [p(u8), i64, c.c_int, c.c_int,
                                              c.c_int, c.c_int, p(u8)]


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it on first use; None if unavailable."""
    global _lib, _tried
    if _lib is not None:
        return _lib
    if _tried or os.environ.get("TNN_NATIVE", "1") in ("0", "false", "off"):
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            # always through make: native/build/ is git-ignored, so a .so
            # found there may predate native/src. make is incremental — a
            # current build costs one stat pass. An explicit TNN_NATIVE_LIB
            # is somebody else's build and is loaded as given.
            if not _SO_OVERRIDE:
                build_native()
            lib = ctypes.CDLL(_SO_PATH)
            _configure(lib)   # AttributeError: a stale override lacks a symbol
            _lib = lib
        except (OSError, RuntimeError, AttributeError,
                subprocess.SubprocessError) as e:
            # the Python fallbacks are complete, so this is not fatal — but
            # it is never silent: the data path just got slower
            last = (str(e).strip().splitlines() or [""])[-1]
            print(f"tnn_tpu.native: python fallback ({type(e).__name__}: "
                  f"{last})", file=sys.stderr)
            _lib = None
    return _lib


def available() -> bool:
    return get_lib() is not None
