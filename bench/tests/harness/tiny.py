"""A harness module for the tests, as a configuration names one: the tiny
cell's random operands and the plain reference, each call recorded in
``conftest.HARNESS_CALLS``."""
from conftest import HARNESS_CALLS, tiny_layers

import reference


def cell_layers(config, seed):
    HARNESS_CALLS.append("cell_layers")
    return tiny_layers(seed)


def reference_rows(host_layers, config, traffic, bits=None, log=None):
    HARNESS_CALLS.append("reference_rows")
    return reference.reference_rows(host_layers, config, traffic, bits=bits,
                                    log=log)
