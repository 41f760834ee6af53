"""How large the linear-attention layers' recurrent states are: the
largest magnitude of an entry of a state after a step's last row, over the
last window's steps, layers and heads (the program's gauge
``delta_rule.state_abs_max``, from each ``GatedDeltaRule`` node's auxiliary
state, brought home in the window's one fetch). With beta up to 2 an
eigenvalue of a step can be -1: a state that grows from window to window
is the first thing a user of this layer asks about."""


def read(run):
    value = (run.get('gauges') or {}).get('delta_rule.state_abs_max')
    return None if value is None else float(value)
