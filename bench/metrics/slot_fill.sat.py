"""slot_fill.sat: queries admitted per beat over the plan's query slots
(the sum of every template's slot capacity), in percent."""


def read(run):
    if not run.beats:
        return None
    slots = sum(t["cap"] for t in run.config["templates"].values())
    admitted = sum(b.stats["admitted"] for b in run.beats)
    return 100.0 * admitted / (slots * len(run.beats))
