"""A number the driver measured itself on the host clock or read from a
counter of the program between steps (``run.values[key]``)."""


def read(run, key):
    return run.values.get(key)
