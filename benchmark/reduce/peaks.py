"""Published peaks of the chips the benchmark may run on, keyed by
``jax.Device.device_kind``. A kind that is not listed is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s in bf16, 393 TOP/s
in int8, 16 GB of HBM2e at 819 GB/s, 1600 Gbit/s of inter-chip
interconnect.
"""

PEAKS = {
    'TPU v5 lite': {'bf16_flops': 197e12, 'int8_ops': 393e12,
                    'hbm_bytes_s': 819e9, 'hbm_bytes': 16e9,
                    'ici_bits_s': 1600e9},
}
PEAKS['TPU v5e'] = PEAKS['TPU v5 lite']


def peaks_of(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError('no published peaks for device kind %r in '
                       'benchmark/reduce/peaks.py; add them with their '
                       'source' % (device_kind,)) from None
