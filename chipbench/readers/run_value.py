"""A number the runner reports as it is (args: key): setup_s."""


def read(args, run):
    value = run.get(args["key"])
    return None if value is None else float(value)
