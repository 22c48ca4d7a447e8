#!/bin/sh
# Proof that the committed files are enough: run one cell from an unpacked
# `git archive` of the index, in a directory that is no git repository.
#
#   here:        git add -A && rm -rf _archive_check && mkdir _archive_check \
#                && git archive "$(git write-tree)" | tar -x -C _archive_check
#   on the chip: chiprun --timeout 2400 -- sh benchmark/tools/archive_proof.sh
#
# Four runs of higgs.train_steady from _archive_check with the cache under the
# checkout: a cold one (everything compiles), a new seed (only the fused
# program compiles), a traced one, and the first seed again (nothing compiles).
# Output goes to chiprun_out/proof_*.log and .err.
cd _archive_check || exit 9
mkdir -p ../chiprun_out
run() {
    env -u JAX_COMPILATION_CACHE_DIR python3 benchmark/run.py \
        --workload higgs.train_steady --seed "$1" --seconds 20 --trace "$2" \
        > "../chiprun_out/proof_$3.log" 2> "../chiprun_out/proof_$3.err"
    echo "proof_$3 seed=$1 trace=$2 rc=$?"
    grep -e "^compile cache" -e "^window" "../chiprun_out/proof_$3.log" | cut -c1-260
    tail -1 "../chiprun_out/proof_$3.log" | cut -c1-420
    du -sm .cache/jax | cut -f1
}
run 7001 0 cold
run 7002 0 warm1
run 7003 1 trace
run 7001 0 warm2
