#!/bin/bash
# Run chip_smoke.py twice on the chip against ONE placed compile cache:
# the second run must report cache hits and far fewer compile seconds,
# and no cache directory other than the placed one may appear.
#   chiprun -- bash tools/chip_smoke_twice.sh
# Run it from the root of a checkout (or of an unpacked `git archive`).
# CHIP_OUT (default ./chiprun_out; relative to that root is fine) is
# where the outputs and the placed cache go. Both runs get a TMPDIR of
# their own inside the checkout, so everything they write is under `.`.
mkdir -p "${CHIP_OUT:-chiprun_out}" tmp/smoke_tmp
OUT=$(cd "${CHIP_OUT:-chiprun_out}" && pwd)
export TMPDIR=$PWD/tmp/smoke_tmp
export JAX_COMPILATION_CACHE_DIR=$OUT/jaxcc
python3 chip_smoke.py --workdir tmp/smoke_ms1 > "$OUT/smoke_run1.out" 2> "$OUT/smoke_run1.err"; rc1=$?
rc2=99
if [ $rc1 -eq 0 ]; then
  python3 chip_smoke.py --workdir tmp/smoke_ms2 > "$OUT/smoke_run2.out" 2> "$OUT/smoke_run2.err"; rc2=$?
fi
echo "pwd=$PWD rc1=$rc1 rc2=$rc2"
echo "cache dirs other than the placed one, under the checkout and the runs' TMPDIR (want none):"
find . "$TMPDIR" -name "*jax_cache*" -not -path "$OUT/jaxcc*" 2>/dev/null | sort -u | head
echo "placed cache: $(du -sh "$OUT/jaxcc" | cut -f1), $(ls "$OUT/jaxcc" | wc -l) files"
rm -rf "$OUT/jaxcc"        # tens of MB of executables: not worth bringing back
echo "== run1"; tail -c 6500 "$OUT/smoke_run1.out"
echo "== run2"; tail -c 5500 "$OUT/smoke_run2.out" 2>/dev/null
echo "== err1"; grep -v "\[INFO\]" "$OUT/smoke_run1.err" | tail -c 2500
echo "== err2"; grep -v "\[INFO\]" "$OUT/smoke_run2.err" 2>/dev/null | tail -c 1500
[ $rc1 -eq 0 ] && [ $rc2 -eq 0 ]
