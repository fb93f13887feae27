"""exchange_roofline: the least time the input's join-line exchange needs
on the cell's interconnect, as a percentage of exchange_s.

The work comes from the generated input alone (benchmark/reference.py
`work()`).  The triples start split by rows over the c chips, so a join
line's incidences (value, capture) start spread over them; the line's
containment counts need the whole line in one place.  Every seed places
the rows at random (benchmark/gen.py relabel), so on average (c - 1)/c of
a line's incidences are on other chips than the one that gathers it.
They cross the interconnect in one of two encodings at the least: 8 bytes
per incidence (value and capture), or one bit per (line, frequent
capture) cell.  So bytes = (c - 1)/c x min(8 x incidences,
lines x captures / 8), and the c chips send them in parallel at the
per-chip ICI bandwidth: least time = bytes / (c x bandwidth).  That is
the expected least time under random row placement, not a strict bound:
on such inputs an exact join-line implementation moves about these bytes
at the least and no chip sends faster, so the share stays under 100%.

Per-chip ICI bandwidth, Google Cloud documentation, "TPU v5e" (system
architecture): 1,600 Gbps of inter-chip interconnect per chip.  Kept here
and not in benchmark/peaks.py; a device kind missing here is an error."""

from benchmark.metrics import exchange_s

ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}


def least_time(work, chips: int, ici_bytes_per_s: float) -> float:
    nbytes = (chips - 1) / chips * min(8.0 * work["incidences"],
                                      work["lines"] * work["captures"] / 8.0)
    return nbytes / (chips * ici_bytes_per_s)


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def read(run):
    seconds = exchange_s.read(run)
    if not seconds:
        return None
    kind = device_kind()
    if kind not in ICI_BYTES_PER_S:
        raise KeyError(f"no published ICI bandwidth for device kind {kind!r}")
    return 100.0 * least_time(run.work, run.cell["chips"],
                              ICI_BYTES_PER_S[kind]) / seconds
