"""Words the conv kernels' launch geometry moves over the Thm 2.1 lower
bound, summed over the cell's layers: the program's own counter
(``DispatchDecision.measured_words`` and ``lower_bound``), recorded while
the cell's programs were traced in set-up. A count; it repeats exactly."""


def read(r):
    ds = [d for d in r.info.get("decisions", ())
          if d.measured_words is not None and d.lower_bound]
    if not ds:
        return None
    return sum(d.measured_words for d in ds) / sum(d.lower_bound for d in ds)
