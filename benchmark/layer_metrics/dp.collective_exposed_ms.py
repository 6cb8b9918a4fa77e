"""The part of ``dp.collective_ms`` during which no other operation ran
on that chip: the exchange the step did not hide."""

from benchmark import harness


def read(view):
    both = harness.load_module("layer_metrics",
                               "dp.collective_ms").per_step_ms(view)
    return None if both is None else both[1]
