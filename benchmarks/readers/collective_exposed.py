"""Time of collective operations during which no other operation ran on the
same device, as a share of the traced window (mean over the chips)."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * run.trace["collective_exposed_s"] / run.trace["window_s"]
