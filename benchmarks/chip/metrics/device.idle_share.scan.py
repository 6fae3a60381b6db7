"""Share of the traced window in which no operation ran on the chip, %."""


def read(run):
    if run["kind"] != "scan" or run.get("trace") is None:
        return None
    return 100.0 * run["trace"].idle_share
