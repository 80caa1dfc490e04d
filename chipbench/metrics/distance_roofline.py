"""Share of the HBM roofline that the distance work reaches, %.

The least time the chip could take for the window's distance
evaluations is the bytes of the rows they read (``n_dist`` float32
rows of ``dim`` elements) over the peak HBM bandwidth;
the operations (3 per element) are far below the compute bound. That
time over the device's busy time is the share. The bytes are the work
the algorithm needs, whatever computes it.
"""

ROW_BYTES_PER_ELEMENT = {"float32": 4}  # the only tier-2 precision in use


def read(run):
    width = ROW_BYTES_PER_ELEMENT[run.config["engine"]["precision"]]
    if run.trace is None or run.peaks is None \
            or not run.counters.get("n_dist"):
        return None
    row_bytes = run.counters["n_dist"] * run.config["dim"] * width
    t_min = row_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * t_min / run.trace.busy_s
