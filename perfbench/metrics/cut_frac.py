"""Cut edges over all edges, by the NumPy reference, averaged over the
window's partitions."""


def read(run):
    done = run.done
    if not done or run.edges == 0:
        return None
    return sum(p.readings["cut"] for p in done) / (len(done) * run.edges)
