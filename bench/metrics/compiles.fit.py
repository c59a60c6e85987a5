"""Executables compiled or loaded from the compilation cache per fit in
the window: the program's `jax.compiles` count. Every shape is warmed in
set-up, so a sound window reads 0."""
from bench import program_trace as pt


def read(view):
    fits = view.result["counts"]["fits"]
    n = pt.total(view.trace, pt.events(view), "jax.compiles")
    return n / fits if fits and n is not None else None
