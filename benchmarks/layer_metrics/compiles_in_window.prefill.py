"""Executor: backend compile requests inside the window
(``analysis.recompile_guard.compile_count`` delta); expected 0."""


def read(run):
    return float(run["compiles_in_window"])
