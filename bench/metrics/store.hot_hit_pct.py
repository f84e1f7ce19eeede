"""Share, in %, of hybrid-store lookups served from the hot tier over the
window: ``TierStats`` hot hits over lookups, summed over every store that
served (each delta publishes a cloned store)."""


def read(run):
    if not run.tier or not run.tier.get("lookups"):
        return None
    return 100.0 * run.tier["hot_hits"] / run.tier["lookups"]
