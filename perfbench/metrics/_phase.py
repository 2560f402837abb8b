"""Mean seconds per partition of some of the program's timer scopes (the
children of its ``partitioning`` scope); None where none of them ran."""


def mean_phase_seconds(run, *names):
    parts = run.partitions
    if not any(name in p.phases for p in parts for name in names):
        return None
    return sum(p.phases.get(name, 0.0) for p in parts for name in names) / len(parts)


def mean_counter(run, attr):
    parts = run.partitions
    return sum(getattr(p, attr) for p in parts) / len(parts) if parts else None
