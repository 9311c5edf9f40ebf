"""Build the native host-path extension:

    python native/setup.py build_ext --build-lib <dir>

siddhi_tpu.native builds this on first import (cached under
siddhi_tpu/_native_build/<hash of native.SOURCES>/, always with --force) and
falls back to the pure-Python encoder, with a WARNING, when the build fails."""

from setuptools import Extension, setup

setup(
    name="siddhi-tpu-native",
    ext_modules=[
        Extension(
            "_siddhi_native",
            sources=["columnar.c"],
            depends=["colring_core.h"],
            extra_compile_args=["-O3"],
        )
    ],
)
