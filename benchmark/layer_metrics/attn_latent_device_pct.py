"""Share of the device's busy time in the latent attention kernels
(forward and backward), from the traced slice."""


def read(run):
    k = run.get('kernels') or {}
    if not k.get('busy') or 'attention_latent' not in k:
        return None
    return 100.0 * k['attention_latent'] / k['busy']
