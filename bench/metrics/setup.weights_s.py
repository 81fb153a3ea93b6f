"""Seconds to make the packed weights on the device from the seed."""


def read(run):
    return run.setup.get("weights_s")
