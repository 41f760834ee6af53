"""Train ImageNet-class networks (ResNet) with Module + KVStore —
BASELINE config #2; the benchmark's `resnet50_v1` configuration builds
the same symbol (`symbols/resnet.py`).

Mirrors example/image-classification/train_imagenet.py: symbolic ResNet,
RecordIO/synthetic data, data-parallel fit over all local devices via
KVStore('device') semantics (on TPU: psum over the mesh inside one
compiled step).

    python train_imagenet.py --network resnet --num-layers 50 \
        --benchmark 1 --batch-size 32 --gpus 0

The device is asked for, as in the reference: ``--gpus 0,1,2,3``
trains on those chips, and without it on ``mx.cpu()``.
"""
import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

import mxnet_tpu as mx
from symbols.resnet import get_symbol


def synthetic_imagenet_iter(batch_size, image_shape, num_classes, samples):
    rng = np.random.RandomState(0)
    data = rng.standard_normal((samples,) + image_shape).astype('float32')
    label = rng.randint(0, num_classes, samples).astype('float32')
    return mx.io.NDArrayIter(data, label, batch_size=batch_size,
                             shuffle=True, label_name='softmax_label')


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--network', default='resnet')
    parser.add_argument('--num-layers', type=int, default=50)
    parser.add_argument('--batch-size', type=int, default=32)
    parser.add_argument('--image-shape', default='3,224,224')
    parser.add_argument('--num-classes', type=int, default=1000)
    parser.add_argument('--num-epochs', type=int, default=1)
    parser.add_argument('--lr', type=float, default=0.1)
    parser.add_argument('--kv-store', default='device')
    parser.add_argument('--gpus', default=None,
                        help='list of chips to run on, e.g. 0 or 0,2,5. '
                             'empty means using cpu')
    parser.add_argument('--benchmark', type=int, default=0,
                        help='use synthetic data (no dataset needed)')
    parser.add_argument('--samples', type=int, default=256)
    parser.add_argument('--data-train', default=None,
                        help='RecordIO file of packed images')
    parser.add_argument('--model-prefix', default=None)
    parser.add_argument('--dtype', default='float32',
                        choices=['float32', 'float16'],
                        help='float16 casts after data so every weight '
                             'trains in half precision (bf16 on TPU '
                             'under MXTPU_F16_AS_BF16)')
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    image_shape = tuple(int(x) for x in args.image_shape.split(','))
    if args.benchmark or not args.data_train:
        train = synthetic_imagenet_iter(args.batch_size, image_shape,
                                        args.num_classes, args.samples)
    else:
        train = mx.io.ImageRecordIter(
            path_imgrec=args.data_train, data_shape=image_shape,
            batch_size=args.batch_size, shuffle=True)

    sym = get_symbol(num_classes=args.num_classes,
                     num_layers=args.num_layers,
                     image_shape=args.image_shape, dtype=args.dtype)
    devs = ([mx.tpu(int(i)) for i in args.gpus.split(',')]
            if args.gpus else mx.cpu())
    mod = mx.mod.Module(symbol=sym, context=devs)
    mod.fit(train,
            eval_metric=['acc'],
            kvstore=args.kv_store,
            optimizer='sgd',
            optimizer_params={'learning_rate': args.lr, 'momentum': 0.9,
                              'wd': 1e-4,
                              'multi_precision': args.dtype == 'float16'},
            initializer=mx.init.Xavier(rnd_type='gaussian',
                                       factor_type='in', magnitude=2),
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 10),
            epoch_end_callback=(mx.callback.do_checkpoint(args.model_prefix)
                                if args.model_prefix else None),
            num_epoch=args.num_epochs)
    arg_params, _ = mod.get_params()
    logging.info('parameters live on %s', sorted(
        {str(d) for v in arg_params.values() for d in v._data.devices()}))
    return mod


if __name__ == '__main__':
    main()
