"""Host-clock milliseconds per graph that the fault sweep spends outside its
batched solves: each ``faults/sweep`` span less the ``spectral/*`` spans
inside it (sample materialisation, padding, connectivity, bounds)."""


def read(rec: dict):
    sweeps = [e for e in rec["spans"] if e["name"] == "faults/sweep"]
    if not sweeps or not rec["graphs"]:
        return None
    spectral = [e for e in rec["spans"] if e["name"].startswith("spectral/")]
    own = 0.0
    for s in sweeps:
        lo, hi = s["ts"], s["ts"] + s["dur"]
        inside = sum(e["dur"] for e in spectral
                     if e["ts"] >= lo and e["ts"] + e["dur"] <= hi)
        own += s["dur"] - inside
    return own / 1e3 / rec["graphs"]
