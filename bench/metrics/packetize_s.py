"""Seconds per sweep in packetization (ordering, quantization, stream
assembly): ``run_sweep``'s ``packetize_s`` stat, host clock, averaged
over the window's sweeps, which run untraced in every run. The stat
spans work that ends in host arrays (the streamed assembler scatters
into NumPy buffers)."""


def read(run):
    vals = [s["stats"].get("packetize_s") for s in run.sweeps]
    if not vals or None in vals:
        return None
    return sum(vals) / len(vals)
