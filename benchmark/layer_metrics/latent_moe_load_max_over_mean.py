"""Rows of the fullest held expert over the mean of the experts held, the
worst layer and step of the last window, under the sigmoid router with its
selection bias: the program's gauge ``moe.load_max_over_mean`` (1 is an
even load; the grouped product's tiles follow the rows, so an uneven load
costs padding, not dropped pairs)."""


def read(run):
    if run['config'].get('scoring_func') != 'sigmoid':
        return None
    value = (run.get('gauges') or {}).get('moe.load_max_over_mean')
    return None if value is None else float(value)
