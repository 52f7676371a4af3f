"""Plain pandas references, one file per query template.

Each ``<template>.py`` gives ``COLUMNS`` (the source columns it reads, by
table) and ``answer(t, p)``: the template's answer over the source frames
``t`` for the placeholder values ``p``, as ``{output column: numpy array}``.
Nothing here imports the program.
"""

import numpy as np


def day(text: str) -> np.datetime64:
    return np.datetime64(text, "D")


def plus_months(text: str, months: int) -> np.datetime64:
    """``date 'text' + interval 'months' month`` for a first-of-month date."""
    return (np.datetime64(text, "M") + months).astype("datetime64[D]") + (
        day(text) - np.datetime64(text, "M").astype("datetime64[D]")
    )


def columns(frame, names) -> dict:
    return {c: frame[c].to_numpy() for c in names}
