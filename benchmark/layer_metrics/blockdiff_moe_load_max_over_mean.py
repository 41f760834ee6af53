"""Rows of the fullest held expert over the mean of the experts held, the
worst layer and step of the last window, under this family's softmax
router (top 8 of 128, no shared expert, no selection bias) on rows of
which half are partly mask ids: the program's gauge
``moe.load_max_over_mean`` (1 is an even load)."""


def read(run):
    if run['config'].get('model_type') != 'sdar_moe':
        return None
    value = (run.get('gauges') or {}).get('moe.load_max_over_mean')
    return None if value is None else float(value)
