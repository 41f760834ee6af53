"""Rows of the fullest held expert over the mean of the experts held, the
worst layer and step of the last window, under this family's sigmoid
router with its expert bias and no shared expert: the program's gauge
``moe.load_max_over_mean`` (1 is an even load; the grouped product's tiles
follow the rows, so an uneven load costs padding and, past twice the even
share, a second pass, never a pair)."""


def read(run):
    if run['config'].get('model_type') != 'lfm2_moe':
        return None
    value = (run.get('gauges') or {}).get('moe.load_max_over_mean')
    return None if value is None else float(value)
