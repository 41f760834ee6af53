#!/bin/bash
# Transformer MFU/long-context sweep: probes whether larger d_model,
# longer sequences, or spc=64 move the decoder's MFU (not measured on the
# attached chip yet), with a long-context (seq 8192, flash-attention
# Pallas) point. One TPU process at a time: run it through the chip tool.
set -u
cd "$(dirname "$0")/.."
OUT=chiprun_out
mkdir -p "$OUT"
STAMP=$(date -u +%Y%m%dT%H%M%SZ)

run () {  # run <tag> <env...>
  tag=$1; shift
  echo "== transformer $tag =="
  env "$@" MXTPU_BENCH_MODEL=transformer \
    timeout 600 python bench.py \
    > "$OUT/bench_tf_${tag}_$STAMP.json" 2> "$OUT/bench_tf_${tag}_$STAMP.log"
  echo "rc=$?"; tail -1 "$OUT/bench_tf_${tag}_$STAMP.json"
}

run d2048L8   MXTPU_BENCH_DMODEL=2048 MXTPU_BENCH_BATCH=4
run spc64     MXTPU_BENCH_STEPS_PER_CALL=64
run seq2048   MXTPU_BENCH_SEQ=2048 MXTPU_BENCH_BATCH=4
run seq8192   MXTPU_BENCH_SEQ=8192 MXTPU_BENCH_BATCH=1
# seq 16384 is past what flash_attention's whole-axis K/V blocks allow
# (it raises; the blockwise K loop is ROADMAP S7)
echo "== done =="
