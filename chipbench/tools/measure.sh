#!/bin/sh
# Full measurement of the cells named on the command line, one after the
# other, from the root of a checkout on a machine with the chips: the
# readings (12 seeds + 3 control seeds, one process), two sets of 6
# untraced runs with the same 6 seeds, and 3 traced runs.  READINGS=0
# leaves the readings out (to take them for every cell first).
#   OUT_DIR=<dir> SECONDS_RUN=10 SEED_BASE=3000000000 \
#     sh chipbench/tools/measure.sh fig2.deep ...
# Output: $OUT_DIR/<cell>.{readings,sets,traced} and their .err files.
SECONDS_RUN=${SECONDS_RUN:-10}
SEED_BASE=${SEED_BASE:-3000000000}
OUT_DIR=${OUT_DIR:-.chipbench_cache/measure}
mkdir -p $OUT_DIR
for w in "$@"; do
  o=$OUT_DIR/$w
  if [ "${READINGS:-1}" = 1 ]; then
    timeout 900 python3 chipbench/readings.py --workload $w --seeds 12 \
      --control-seeds 3 --first-seed $((SEED_BASE + 100)) \
      --seconds $SECONDS_RUN > $o.readings 2> $o.readings.err
    echo "$w readings rc $?"
  fi
  for set in A B; do
    for i in 1 2 3 4 5 6; do
      timeout 400 python3 chipbench/run.py --workload $w --seed $((SEED_BASE + i)) \
        --seconds $SECONDS_RUN --trace 0 > $o.run.out 2> $o.run.err
      rc=$?
      echo "$set $i rc=$rc $(tail -1 $o.run.out)" >> $o.sets
      tail -14 $o.run.err >> $o.sets.err
    done
  done
  for i in 1 2 3; do
    timeout 400 python3 chipbench/run.py --workload $w --seed $((SEED_BASE + 50 + i)) \
      --seconds $SECONDS_RUN --trace 1 > $o.run.out 2> $o.run.err
    rc=$?
    echo "T $i rc=$rc $(tail -1 $o.run.out)" >> $o.traced
    tail -14 $o.run.err >> $o.traced.err
  done
  echo "$w done"; cut -c1-400 $o.sets | head -12
done
