"""binding_beats.sat: share of beats in which some template's slots were
all taken, so that admission, not arrivals, bounded the beat (percent)."""


def read(run):
    if not run.beats:
        return None
    caps = {n: t["cap"] for n, t in run.config["templates"].items()}
    full = sum(1 for b in run.beats
               if any(k >= caps[n] for n, k in b.answered.items()))
    return 100.0 * full / len(run.beats)
