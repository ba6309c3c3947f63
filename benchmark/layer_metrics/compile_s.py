"""Kernels: seconds inside the backend compiler during set-up, from
JAX's monitoring events (0 when every program came from the cache)."""


def read(view):
    return view["setup"]["compile_s"]
