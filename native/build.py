#!/usr/bin/env python
"""Build libtinysql_native.so (g++ -O3).  tinysql_tpu/native.py calls
`ensure()` on first use; `build()` always compiles and is safe to run
directly.

Processes that start together on a tree without the library (every xdist
worker collects tests/test_native.py) must not write one path at once, and
none may load what another is still writing.  So one builder at a time
(an exclusive `flock` on the source file, released by the kernel if its
holder dies), the compiler writes a temporary name in this directory, and
`os.replace` puts it onto the final name: a process sees no library or
all of it, a library that is mapped is never rewritten, and a process
that waited for another's build compiles nothing."""
import contextlib
import fcntl
import os
import subprocess
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "tinysql_native.cpp")
OUT = os.path.join(HERE, "libtinysql_native.so")


@contextlib.contextmanager
def _one_builder():
    with open(SRC, "rb") as src:
        fcntl.flock(src, fcntl.LOCK_EX)
        yield


def _compile() -> None:
    fd, tmp = tempfile.mkstemp(prefix="libtinysql_native.",
                               suffix=".tmp.so", dir=HERE)
    os.close(fd)
    try:
        subprocess.run(["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                        SRC, "-o", tmp], check=True, capture_output=True)
        os.chmod(tmp, 0o755)
        os.replace(tmp, OUT)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def build() -> str:
    with _one_builder():
        _compile()
    return OUT


def ensure() -> str:
    """Build only if the library is missing or older than its source."""
    with _one_builder():
        if not (os.path.exists(OUT)
                and os.path.getmtime(OUT) >= os.path.getmtime(SRC)):
            _compile()
    return OUT


if __name__ == "__main__":
    print(build())
