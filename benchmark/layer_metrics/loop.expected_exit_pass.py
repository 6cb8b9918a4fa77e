"""The pass a token would leave the loop at, were the exit gate acted
on: the mean of ``exit_expected_pass`` over the window's
``serve/decode_window`` spans (the decode program computes the exit
distribution of every live lane on the device and returns its mean pass,
1-based).  On seeded weights it says nothing of a trained model; it
shows that the gate runs in the served program.  A program without the
stat gives None."""

import statistics

from benchmark import program_spans


def read(view):
    passes = program_spans.stat(view, "serve/decode_window",
                                "exit_expected_pass")
    return statistics.fmean(passes) if passes else None
