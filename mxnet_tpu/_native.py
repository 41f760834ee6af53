"""Loader for the native runtime library (src/*.cc → libmxtpu.so).

The reference ships its native core as libmxnet.so loaded by
python/mxnet/base.py (_load_lib); here the native layer is the host-side
runtime — dependency engine, pooled storage, RecordIO, profiler — and
this module finds or builds it, then exposes ctypes bindings. Pure-Python
fallbacks exist for every feature, so a missing compiler degrades
gracefully (LIB is None and callers check :func:`available`).
"""
import ctypes
import os
import subprocess
import threading

__all__ = ['get_lib', 'available', 'check_call', 'NativeError']

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_DIR), 'src')
_SO = os.path.join(_DIR, 'libmxtpu.so')
_SOURCES = ('engine.cc', 'storage.cc', 'recordio.cc', 'profiler.cc')

_lock = threading.Lock()
_lib = None
_tried = False


class NativeError(RuntimeError):
    pass


def _stale():
    if not os.path.exists(_SO):
        return True
    so_mtime = os.path.getmtime(_SO)
    for f in _SOURCES + ('mxtpu.h',):
        p = os.path.join(_SRC, f)
        if os.path.exists(p) and os.path.getmtime(p) > so_mtime:
            return True
    return False


def _build():
    # several processes of one checkout may build at once (test workers):
    # each links its own file and renames it into place, so no process
    # ever loads a library that another is still writing
    srcs = [os.path.join(_SRC, f) for f in _SOURCES]
    tmp = '%s.%d.tmp' % (_SO, os.getpid())
    cmd = ['g++', '-std=c++17', '-O2', '-fPIC', '-Wall', '-pthread',
           '-shared', '-o', tmp] + srcs
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True,
                       timeout=300)
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _bind(lib):
    import ctypes as C
    lib.MXTGetLastError.restype = C.c_char_p
    lib.MXTNowUS.restype = C.c_int64
    protos = {
        'MXTEngineCreate': [C.c_int, C.POINTER(C.c_void_p)],
        'MXTEngineFree': [C.c_void_p],
        'MXTEngineNewVar': [C.c_void_p, C.POINTER(C.c_void_p)],
        'MXTEngineDeleteVar': [C.c_void_p, C.c_void_p],
        'MXTEnginePushSync': [C.c_void_p, C.c_void_p, C.c_void_p,
                              C.POINTER(C.c_void_p), C.c_int,
                              C.POINTER(C.c_void_p), C.c_int,
                              C.c_int, C.c_char_p],
        'MXTEnginePushAsync': [C.c_void_p, C.c_void_p, C.c_void_p,
                               C.POINTER(C.c_void_p), C.c_int,
                               C.POINTER(C.c_void_p), C.c_int,
                               C.c_int, C.c_char_p],
        'MXTEngineOprComplete': [C.c_void_p],
        'MXTEngineWaitForVar': [C.c_void_p, C.c_void_p],
        'MXTEngineWaitForAll': [C.c_void_p],
        'MXTEnginePendingOps': [C.c_void_p, C.POINTER(C.c_int64)],
        'MXTStorageAlloc': [C.c_size_t, C.POINTER(C.c_void_p)],
        'MXTStorageFree': [C.c_void_p],
        'MXTStorageDirectFree': [C.c_void_p],
        'MXTStorageReleaseAll': [],
        'MXTStorageStats': [C.POINTER(C.c_int64)],
        'MXTRecordIOWriterCreate': [C.c_char_p, C.POINTER(C.c_void_p)],
        'MXTRecordIOWriterWrite': [C.c_void_p, C.c_char_p, C.c_size_t],
        'MXTRecordIOWriterTell': [C.c_void_p, C.POINTER(C.c_size_t)],
        'MXTRecordIOWriterFree': [C.c_void_p],
        'MXTRecordIOReaderCreate': [C.c_char_p, C.POINTER(C.c_void_p)],
        'MXTRecordIOReaderNext': [C.c_void_p, C.POINTER(C.c_void_p),
                                  C.POINTER(C.c_size_t)],
        'MXTRecordIOReaderSeek': [C.c_void_p, C.c_size_t],
        'MXTRecordIOReaderTell': [C.c_void_p, C.POINTER(C.c_size_t)],
        'MXTRecordIOReaderFree': [C.c_void_p],
        'MXTProfilerSetState': [C.c_int],
        'MXTProfilerAddEvent': [C.c_char_p, C.c_char_p, C.c_int64, C.c_int64],
        'MXTProfilerDump': [C.c_char_p],
    }
    for name, argtypes in protos.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        if name not in ('MXTGetLastError', 'MXTNowUS'):
            fn.restype = C.c_int
    return lib


def get_lib():
    """The loaded CDLL, building it first if needed; None if unavailable.

    Disable with MXTPU_NO_NATIVE=1 (forces the pure-Python fallbacks)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        from .config import flags as _flags
        if _flags.get('MXTPU_NO_NATIVE'):
            return None
        try:
            if _stale():
                _build()
            _lib = _bind(ctypes.CDLL(_SO))
        except Exception:
            _lib = None
        return _lib


def available():
    return get_lib() is not None


def check_call(ret):
    """Raise NativeError with MXTGetLastError on nonzero return
    (reference base.py check_call)."""
    if ret != 0:
        lib = get_lib()
        msg = lib.MXTGetLastError().decode() if lib else 'native call failed'
        raise NativeError(msg)


# ctypes callback types matching src/mxtpu.h
SYNC_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
ASYNC_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_void_p)
