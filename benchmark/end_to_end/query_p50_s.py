"""Median client-side seconds from POST /v1/statement to the last page,
over every statement the window issued that finished."""

from statistics import median


def read(run):
    return median([s.seconds for s in run.finished])
