"""Percent of the window the store spent folding the sealed delta into
the base (engine/store.py maintain/_fold), from the growth of
engine_op_seconds{path="fold"}: host merge and device row scatter."""


def read(run):
    return run.share_of_window("engine_op_seconds", path="fold")
