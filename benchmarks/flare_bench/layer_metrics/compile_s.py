"""Seconds spent lowering and compiling the cell's queries in set-up
(``CompileStats.lower_s + compile_s``; with a warm JAX cache, loading
them from it)."""


def read(run):
    return run["compile_s"]
