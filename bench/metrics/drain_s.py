"""Seconds per sweep in the request drain: ``run_sweep``'s ``simulate_s``
stat (``simulate_batch``, sharded or not), host clock, averaged over the
window's sweeps, which run untraced in every run. The stat ends when
every lane's results are host integers."""


def read(run):
    vals = [s["stats"].get("simulate_s") for s in run.sweeps]
    if not vals or None in vals:
        return None
    return sum(vals) / len(vals)
