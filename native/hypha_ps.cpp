// Native tensor math for the parameter-server outer step.
//
// The reference implements its only native numerical component in Rust with
// candle-core: streaming averaging of worker pseudo-gradients over mmapped
// SafeTensors plus the Nesterov outer update
// (reference: crates/worker/src/executor/parameter_server.rs:331-446).
// This is the C++ equivalent: flat float32 kernels invoked via ctypes, with
// Python owning SafeTensors metadata. Each is one pass in place — the job
// is memory-bandwidth bound. Two run in the program: fold_scaled_f32 (a
// delta into the round's sum) and fused_mean_nesterov_inplace_f32 (the sum
// to the update); nesterov_update_f32 is the plain form the tests hold the
// in-place pass to.
//
// Fixes folded in (reference TODO parameter_server.rs:192-194): the mean is
// one sample-weighted sum over all N workers divided once, not
// order-dependent pairwise averaging.
//
// Build: g++ -O3 -march=native -shared -fPIC ... (see hypha_tpu/native.py)

#include <algorithm>
#include <cstddef>
#include <cstdint>
#include <system_error>
#include <thread>
#include <vector>

// Runs body(lo, hi) over [0, n) split among up to `threads` threads, the
// caller's included. A thread gets at least 2^19 elements (a leaf under
// about a million runs on the caller alone), and ranges start on a cache
// line. Returns the number of threads that ran.
template <typename Body>
static int64_t split_over_threads(int64_t n, int64_t threads, Body body) {
  const int64_t kMinPerThread = int64_t{1} << 19;
  int64_t t = std::max<int64_t>(1, std::min(threads, n / kMinPerThread));
  int64_t per = ((n + t - 1) / t + 15) / 16 * 16;
  std::vector<std::thread> pool;
  int64_t lo = 0;
  for (int64_t k = 1; k < t && lo + per < n; ++k, lo += per) {
    try {
      pool.emplace_back(body, lo, lo + per);
    } catch (const std::system_error &) {
      break;  // no thread to be had: the caller does the rest itself
    }
  }
  body(lo, n);
  for (auto &th : pool) th.join();
  return static_cast<int64_t>(pool.size()) + 1;
}

extern "C" {

// Nesterov outer step, in place:
//   m <- mu * m + g
//   update <- lr * (mu * m + g)
// matching torch SGD(nesterov=True) semantics the reference golden-tests
// against (parameter_server.rs:448-524).
void nesterov_update_f32(float *momentum, const float *grad, float *update_out,
                         int64_t n, float lr, float mu) {
  for (int64_t i = 0; i < n; ++i) {
    float m = mu * momentum[i] + grad[i];
    momentum[i] = m;
    update_out[i] = lr * (mu * m + grad[i]);
  }
}

// The parameter server's outer step over one leaf, in place and in one pass:
//   g = acc / denom;  m <- mu * m + g;  acc <- lr * (mu * m + g)
// `acc` is the round's partial sum (sum of samples * delta) and comes back
// as the update; `momentum` is the resident outer state. The division and
// the two Nesterov lines are the expressions of RoundAccum.mean() and
// nesterov_update_f32, in their order, so the result is bit-equal to the
// two of them in series. Elementwise, so how a leaf is split over threads
// cannot change a bit either.
static void mean_nesterov_range(float *__restrict__ acc,
                                float *__restrict__ momentum, int64_t lo,
                                int64_t hi, float denom, float lr, float mu) {
  for (int64_t i = lo; i < hi; ++i) {
    float g = acc[i] / denom;
    float m = mu * momentum[i] + g;
    momentum[i] = m;
    acc[i] = lr * (mu * m + g);
  }
}

int64_t fused_mean_nesterov_inplace_f32(float *acc, float denom,
                                        float *momentum, int64_t n, float lr,
                                        float mu, int64_t threads) {
  return split_over_threads(n, threads, [=](int64_t lo, int64_t hi) {
    mean_nesterov_range(acc, momentum, lo, hi, denom, lr, mu);
  });
}

// One delta folded into the round's partial sum where the sum lies
// (RoundAccum): the round's first fold overwrites, acc = scale * x, and a
// later one adds, acc = acc + scale * x. The product is rounded to f32
// before the sum, two roundings and no fused multiply-add, so the sum is
// bit-equal to numpy's `prev += scale * x`, which the durable journal's
// replay and the reduce tree rest on. g++ contracts a*b+c by default in
// C++ (-ffp-contract=fast, ISO mode or not), also across statements, so
// the adding loop turns contraction off for itself; tests/test_native.py
// holds it. When overwriting, x may be acc itself: the bytes of a delta
// read straight into the sum's buffer are scaled where they landed.
static void scale_range(float *acc, const float *x, int64_t lo, int64_t hi,
                        float scale) {
  for (int64_t i = lo; i < hi; ++i) acc[i] = scale * x[i];
}

__attribute__((optimize("fp-contract=off"))) static void add_scaled_range(
    float *__restrict__ acc, const float *__restrict__ x, int64_t lo,
    int64_t hi, float scale) {
  for (int64_t i = lo; i < hi; ++i) acc[i] = acc[i] + scale * x[i];
}

int64_t fold_scaled_f32(float *acc, const float *x, float scale, int64_t n,
                        int overwrite, int64_t threads) {
  return split_over_threads(n, threads, [=](int64_t lo, int64_t hi) {
    if (overwrite) {
      scale_range(acc, x, lo, hi, scale);
    } else {
      add_scaled_range(acc, x, lo, hi, scale);
    }
  });
}

}  // extern "C"
