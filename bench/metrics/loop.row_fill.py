"""Share of the loop engine's row-slots that did work: the sum of each
row's own ACK-complete slot over the sum, over dispatches, of fused rows x
slots the loop ran (the slowest row's).  Rows finished early idle in the
fused ``while_loop``.  Moves ``points_per_s``."""


def read(ctx):
    if ctx["engine"] != "loop":
        return None
    useful = total = 0.0
    for (recs, spans) in zip(_per_campaign(ctx), ctx["spans"]):
        i = 0
        for sp in spans:
            if sp["kind"] != "dispatch":
                continue
            rows = recs[i:i + sp["n_points"]]
            i += sp["n_points"]
            useful += sum(r["cct_acked"] for r in rows)
            total += sp["n_points"] * sp["slots_run"]
    return useful / total if total else None


def _per_campaign(ctx):
    recs, out = ctx["records"], []
    for spans in ctx["spans"]:
        n = sum(sp["n_points"] for sp in spans if sp["kind"] == "dispatch")
        out.append(recs[:n])
        recs = recs[n:]
    return out
