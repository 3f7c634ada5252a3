# two sets of runs of one cell, the same seeds in both: sets.sh <cell> <seconds> <out dir> <seed> ...
cell=$1; seconds=$2; out=$3; shift 3
mkdir -p $out
for set in 1 2; do for seed in "$@"; do
  python3 benchmarks/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 > $out/set$set.$seed.out 2> $out/set$set.$seed.err
  echo "set$set seed $seed rc=$? $(tail -1 $out/set$set.$seed.out | cut -c1-600)"
done; done
