"""How far the streams' mixing matrices are from doubly stochastic: the
largest distance of a row or column sum of ``M_T`` from 1 over the last
window's steps, mixing nodes and tokens, after the configuration's
Sinkhorn rounds (the program's gauge ``hyper.res_dev_max``, from each
``HyperPre`` node's auxiliary state, brought home in the window's one
fetch). 0 is exact; what is left mixes the streams' norms."""


def read(run):
    value = run.get('hyper_res_dev_max')
    return None if value is None else float(value)
