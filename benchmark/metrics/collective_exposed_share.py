"""Collectives: time in collective operations during which no other
operation ran on that chip, over the traced window; in percent, averaged
over the chips (lib/tracered.py)."""


def read(run):
    t = run.get("trace")
    if not t or not t.get("collective_s"):
        return None    # no collective in the trace: nothing to read
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
