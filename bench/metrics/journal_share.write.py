"""Percent of the window the store spent appending and flushing journal
records (engine/store.py _write, ckpt/journal.py), from the growth of
engine_op_seconds{path="journal"}."""


def read(run):
    return run.share_of_window("engine_op_seconds", path="journal")
