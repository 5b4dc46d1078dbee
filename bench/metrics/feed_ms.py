"""Median over the window's steps of the host time of the program's
``liver/batch`` span: the batch's tokens fetched and put on the device.
Also logs the phase split of the slowest steps and of set-up."""

from bench import phases

LAYER = "input feed"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return phases.feed_ms(run)
