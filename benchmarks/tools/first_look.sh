# A first look at one cell on the chip: a traced run with the trace described
# by hand, two plain runs (the second must find every program in the cache),
# and the control and fault readings on two seeds.
#   chiprun --chips 1 --timeout 1800 -- bash benchmarks/tools/first_look.sh <workload> <seed-base>
set -x
mkdir -p chiprun_out
W=$1
S=${2:-4294967301}
BENCH_KEEP_TRACE_DESCRIPTION=chiprun_out/trace_desc_$W.txt python3 benchmarks/run.py --workload $W --seed $S --seconds 5 --trace 1 > chiprun_out/first_${W}_trace.out 2> chiprun_out/first_${W}_trace.err; echo rc=$?
tail -n 5 chiprun_out/first_${W}_trace.err; tail -n 1 chiprun_out/first_${W}_trace.out
python3 benchmarks/run.py --workload $W --seed $((S+1)) --seconds 5 --trace 0 2> chiprun_out/first_${W}_a.err | tail -n 1; tail -n 5 chiprun_out/first_${W}_a.err
python3 benchmarks/run.py --workload $W --seed $((S+2)) --seconds 5 --trace 0 2> chiprun_out/first_${W}_b.err | tail -n 1; tail -n 5 chiprun_out/first_${W}_b.err
if [ -n "$3" ]; then python3 benchmarks/tools/calibrate.py --workload $W --seeds $3 --controls 2 --out chiprun_out/calibrate_$W.jsonl 2> chiprun_out/first_${W}_cal.err; tail -n 5 chiprun_out/first_${W}_cal.err; fi
