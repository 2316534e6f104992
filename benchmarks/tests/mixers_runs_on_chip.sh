#!/bin/sh
# What ISSUE 50 asks of the chip, a part a call (chiprun -- sh benchmarks/tests/mixers_runs_on_chip.sh <part>):
#   step1            three seeds: naive and start point, counters, device ms by
#                    operation, check, the three controls, fence gap, peak;
#                    then the start point alone at blocks of 4096 x 2048
#   runs SEED:TRACE  whole runs of the cell from this tree, one a pair
#   parent           the cell's command from .bench_checkout/parent: refused at once
#   pair SEED        trinity-attn32k.climb parent | change | change | parent
#   final            twelve whole runs from .bench_checkout/change (git archive
#                    of the tree; the seventh seed is the one whose probe read
#                    naive's fence an ulp apart before the one-shot program was
#                    the loop run once), the pre-declared cut and three more if
#                    they force it; then .bench_checkout/overlay (the parent with
#                    this benchmark laid over): the new cell, an old cell traced
# Every run's last stdout line lands in chiprun_out/mixers.<part>.txt.
set -u
W=nemotron3-nano-mixers-prefill.climb
S=benchmarks/tests/mixers_step1_on_chip.py
O=chiprun_out
mkdir -p $O
part=$1; shift
case $part in
step1)
  python $S --workload $W --seeds 2147483659,2147483693,2147483713 --control \
    > $O/mixers_step1.txt 2> $O/mixers_step1.err
  echo "rc=$?" >> $O/mixers_step1.txt
  python $S --workload $W --seeds 2147483659 --blocks 4096x2048 --skip-naive \
    --out mixers_step1.b4096 > $O/mixers_step1.b4096.txt 2> $O/mixers_step1.b4096.err
  echo "rc=$?" >> $O/mixers_step1.b4096.txt
  tail -c 2000 $O/mixers_step1.err; tail -c 8000 $O/mixers_step1.txt
  tail -c 3000 $O/mixers_step1.b4096.txt ;;
runs)
  for st in "$@"; do
    seed=${st%%:*}; trace=${st##*:}
    t0=$(date +%s)
    python benchmarks/run.py --workload $W --seed $seed --seconds 51 --trace $trace \
      > $O/mixers.run.$seed.t$trace.out 2> $O/mixers.run.$seed.t$trace.err
    echo "seed $seed trace $trace rc=$? seconds=$(( $(date +%s) - t0 ))" | tee -a $O/mixers.runs.txt
    tail -n 1 $O/mixers.run.$seed.t$trace.out | tee -a $O/mixers.runs.txt | cut -c1-1800
    grep -E "^(set-up|window|epilogue|clock)" $O/mixers.run.$seed.t$trace.out | tee -a $O/mixers.runs.txt
    if [ $trace = 1 ]; then cp benchmarks/out/$W.seed$seed/record.trace1.json $O/mixers.record.$seed.json; fi
  done ;;
parent)
  t0=$(date +%s)
  (cd .bench_checkout/parent && python benchmarks/run.py --workload $W --seed 7 --seconds 51 --trace 0) \
    > $O/mixers.parent.out 2> $O/mixers.parent.err
  echo "parent rc=$? seconds=$(( $(date +%s) - t0 ))" | tee $O/mixers.parent.txt
  tail -n 3 $O/mixers.parent.err | tee -a $O/mixers.parent.txt ;;
pair)
  seed=$1
  for side in parent change change parent; do
    root=.; [ $side = parent ] && root=.bench_checkout/parent
    (cd $root && python benchmarks/run.py --workload trinity-attn32k.climb --seed $seed --seconds 51 --trace 0) \
      > $O/mixers.pair.$side.out 2> $O/mixers.pair.$side.err
    echo "$side rc=$?: $(tail -n 1 $O/mixers.pair.$side.out | cut -c1-400)" | tee -a $O/mixers.pair.txt
  done ;;
final)
  # twelve whole runs from the committed files alone (.bench_checkout/change =
  # git archive of the tree), the sixth traced; the pre-declared cut if two of
  # them pass 170 s or a peak passes 14 GB, and three runs at the cut then;
  # the parent with this benchmark laid over it: the new cell refused at
  # once, an old cell traced
  cd .bench_checkout/change || exit 2
  mkdir -p $O
  sh $0 runs 2147484079:0 2147484103:0 2147484157:0 2147484211:0 2147484259:0 2147484287:1 \
    2147485063:0 2147485217:0 2147485249:0 2147485291:0 2147485333:0 2147485367:0
  python - <<'PY'
import json, re
cfg = "benchmarks/configs/nemotron3-nano-mixers-prefill.json"
secs, peaks = [], []
for line in open("chiprun_out/mixers.runs.txt"):
    m = re.match(r"seed \d+ trace \d rc=\d+ seconds=(\d+)", line)
    if m:
        secs.append(int(m.group(1)))
    elif line.startswith("{"):
        peaks.append(json.loads(line)["device"]["memory_peak_bytes"] / 1e9)
slow = sum(s > 170 for s in secs)
forced = slow >= 2 or (peaks and max(peaks) > 14.0) or len(peaks) < len(secs)
print(f"whole runs: seconds {secs}, peaks {[round(p, 2) for p in peaks]}: cut "
      f"{'FORCED' if forced else 'not taken'}")
if forced:
    c = json.load(open(cfg))
    c["shapes"]["tokens"] = 8192
    c["shapes"]["prompt_lens"] = c["shapes"]["prompt_lens"][:6]
    json.dump(c, open(cfg, "w"), indent=2)
    open("chiprun_out/mixers.cut_taken", "w").write(f"{secs} {peaks}\n")
PY
  if [ -f $O/mixers.cut_taken ]; then
    mv $O/mixers.runs.txt $O/mixers.runs.full.txt
    sh $0 runs 2147484343:0 2147484367:0 2147484391:1
  fi
  cd ../overlay || exit 2
  mkdir -p $O
  for wt in "$W:0" "trinity-attn32k.climb:1"; do
    w=${wt%%:*}; t0=$(date +%s)
    python benchmarks/run.py --workload $w --seed 2147484421 --seconds 51 --trace ${wt##*:} \
      > $O/mixers.overlay.$w.out 2> $O/mixers.overlay.$w.err
    echo "overlay $w rc=$? seconds=$(( $(date +%s) - t0 ))" | tee -a $O/mixers.overlay.txt
    tail -n 2 $O/mixers.overlay.$w.err | cut -c1-300 | tee -a $O/mixers.overlay.txt
    tail -n 1 $O/mixers.overlay.$w.out | cut -c1-1500 | tee -a $O/mixers.overlay.txt
  done
  cd ../..
  mkdir -p $O/final
  cp .bench_checkout/change/$O/mixers.* .bench_checkout/overlay/$O/mixers.overlay.* $O/final/ ;;
*) echo "unknown part $part"; exit 2 ;;
esac
