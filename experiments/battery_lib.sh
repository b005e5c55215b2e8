# Shared helpers for the TPU measurement batteries (sourced, not run).
#
# Persistent XLA compilation cache: every battery step is its own process
# and gpt-7b program compilation takes minutes cold; identical
# programs (same engine config) hit the cache and build in seconds.
# Same path as utils/platform.enable_compile_cache: <checkout>/.jax_cache.
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)/.jax_cache}"
mkdir -p "$JAX_COMPILATION_CACHE_DIR"
#   run <name> <timeout-s> <cmd...>   — timeboxed step, log + rc to $OUT
#   tpu_guard                          — abort unless the ACTIVE backend is
#                                        TPU (jax.devices() printing a CPU
#                                        fallback exits 0 and would let a
#                                        whole battery record CPU times
#                                        against TPU peaks)

run() {
  local name=$1 to=$2; shift 2
  echo "=== $name ==="
  timeout "$to" "$@" > "$OUT/$name.log" 2>&1
  local rc=$?
  tail -3 "$OUT/$name.log"
  echo "rc=$rc" >> "$OUT/$name.log"
}

tpu_guard() {
  timeout 90 python -c "
import sys
import jax
ok = jax.default_backend() == 'tpu'
print(jax.devices(), 'backend=', jax.default_backend())
sys.exit(0 if ok else 1)
" || { echo "TPU backend unavailable; aborting battery"; exit 1; }
}
