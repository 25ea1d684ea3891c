"""Prebuild the native solver library: ``python -m cluster_tools_tpu.native.build``."""

from . import available

if __name__ == "__main__":
    ok = available()
    print("native solvers:", "OK" if ok else "BUILD FAILED (python fallbacks active)")
    raise SystemExit(0 if ok else 1)
