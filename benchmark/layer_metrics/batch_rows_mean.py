"""Mean rows per dispatch of the batcher (its ``dispatch_log``)."""


def read(run):
    log = (run.get('serve') or {}).get('dispatch_log')
    return sum(r[0] for r in log) / len(log) if log else None
