# The two full sets of a cell (6 runs each, the same seeds in both) and three
# traced runs, as the benchmark's contract asks before a bound is set.
#   chiprun --chips 1 --timeout 3000 -- bash benchmarks/tools/sets.sh <workload> <seconds> <seed-base> [tag] [notrace]
# Result lines go to chiprun_out/sets_<workload>.jsonl; benchmarks/tools/spread.py reads them.
W=$1; SECS=$2; S=$3; TAG=${4:-$W}
mkdir -p chiprun_out
for SET in 1 2; do
  for I in 0 1 2 3 4 5; do
    python3 benchmarks/run.py --workload $W --seed $((S+I)) --seconds $SECS --trace 0 2> chiprun_out/sets_${TAG}.err | tail -n 1 \
      | sed "s/^{/{\"set\": $SET, \"seed\": $((S+I)), /" >> chiprun_out/sets_${TAG}.jsonl
    tail -n 2 chiprun_out/sets_${TAG}.err
  done
done
[ -n "$5" ] && exit 0
for I in 10 11 12; do
  python3 benchmarks/run.py --workload $W --seed $((S+I)) --seconds $SECS --trace 1 2> chiprun_out/sets_${TAG}.err | tail -n 1 \
    | sed "s/^{/{\"set\": 0, \"seed\": $((S+I)), /" >> chiprun_out/sets_${TAG}.jsonl
  tail -n 2 chiprun_out/sets_${TAG}.err
done
