// Standalone C++ serving harness — Python-free model serving (capability
// parity with the reference's Python-free path: paddle/fluid/train/demo/
// demo_trainer.cc loads ProgramDescs and runs them from C++, and the
// reference's inference/tests/api analyzer latency tests time the
// predictor; here we load a save_inference_model StableHLO artifact,
// serve it via PJRT, and report p50/p99 latency).
//
// Usage: ptserve <model_dir> <pjrt_plugin.so> [batch] [iters] [warmup]
//   Feeds zeros shaped per the manifest's feed_shapes/feed_dtypes (the
//   leading/-1 dim replaced by [batch]). iters > 1 times every run and
//   prints a latency summary JSON line (p50/p99/mean ms, examples/sec).
//   Exit 0 on success.

#include <algorithm>
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

extern "C" {
void* ptpred_load(const char* model_dir);
int ptpred_ok(void* h);
const char* ptpred_error(void* h);
int ptpred_compile(void* h, const char* plugin_path);
int ptpred_num_feeds(void* h);
const char* ptpred_feed_name(void* h, int i);
int ptpred_feed_rank(void* h, int i);
int64_t ptpred_feed_dim(void* h, int i, int d);
const char* ptpred_feed_dtype(void* h, int i);
int ptpred_feed_elem_size(void* h, int i);
int ptpred_num_fetches(void* h);
const char* ptpred_fetch_name(void* h, int i);
int ptpred_run(void* h, const void** feed_ptrs, const int64_t* dims,
               const int* ranks);
int ptpred_out_rank(void* h, int i);
int64_t ptpred_out_dim(void* h, int i, int d);
const void* ptpred_out_data(void* h, int i, int64_t* nbytes);
void ptpred_destroy(void* h);
}

int main(int argc, char** argv) {
  if (argc < 3) {
    fprintf(stderr,
            "usage: %s <model_dir> <pjrt_plugin.so> [batch] [iters] "
            "[warmup]\n",
            argv[0]);
    return 64;
  }
  int64_t batch = argc > 3 ? atoll(argv[3]) : 1;
  int iters = argc > 4 ? atoi(argv[4]) : 1;
  int warmup = argc > 5 ? atoi(argv[5]) : 2;
  void* p = ptpred_load(argv[1]);
  if (!ptpred_ok(p)) {
    fprintf(stderr, "load failed: %s\n", ptpred_error(p));
    return 1;
  }
  int nf = ptpred_num_feeds(p);
  printf("model loaded: %d feeds, %d fetches\n", nf,
         ptpred_num_fetches(p));
  if (!ptpred_compile(p, argv[2])) {
    fprintf(stderr, "compile failed: %s\n", ptpred_error(p));
    return 2;
  }
  // pre-pass: resolve the EFFECTIVE batch before sizing any buffer —
  // a fixed-shape artifact (jit.save's concrete fallback) pins it to
  // the traced leading dim; an override would shape-mismatch at PJRT
  // execute with no useful message, and feeds must agree on it
  for (int i = 0; i < nf; i++) {
    int rank = ptpred_feed_rank(p, i);
    if (rank < 1) continue;
    int64_t d0 = ptpred_feed_dim(p, i, 0);
    if (d0 > 0 && d0 != batch) {
      if (argc > 3)
        fprintf(stderr,
                "note: feed %s has fixed batch %lld; ignoring "
                "requested batch %lld\n",
                ptpred_feed_name(p, i), (long long)d0, (long long)batch);
      batch = d0;
    }
  }
  // zero-filled feeds shaped from the manifest; negative/polymorphic
  // dims become the resolved [batch]
  std::vector<std::vector<uint8_t>> storage(nf);
  std::vector<const void*> ptrs(nf);
  std::vector<int64_t> dims;
  std::vector<int> ranks(nf);
  for (int i = 0; i < nf; i++) {
    int rank = ptpred_feed_rank(p, i);
    if (rank < 0) {  // no manifest shape: legacy demo fallback (B, 784)
      rank = 2;
      dims.push_back(batch);
      dims.push_back(784);
      storage[i].assign((size_t)batch * 784 * 4, 0);
    } else {
      size_t elems = 1;
      for (int d = 0; d < rank; d++) {
        int64_t dim = ptpred_feed_dim(p, i, d);
        if (dim < 0) dim = batch;
        dims.push_back(dim);
        elems *= (size_t)dim;
      }
      int esz = ptpred_feed_elem_size(p, i);
      if (esz <= 0) {
        fprintf(stderr, "unsupported feed dtype %s\n",
                ptpred_feed_dtype(p, i));
        return 4;
      }
      storage[i].assign(elems * (size_t)esz, 0);
    }
    ranks[i] = rank;
    ptrs[i] = storage[i].data();
  }
  std::vector<double> lat_ms;
  lat_ms.reserve(iters);
  for (int it = 0; it < warmup + iters; it++) {
    auto t0 = std::chrono::steady_clock::now();
    if (!ptpred_run(p, ptrs.data(), dims.data(), ranks.data())) {
      fprintf(stderr, "run failed: %s\n", ptpred_error(p));
      return 3;
    }
    auto t1 = std::chrono::steady_clock::now();
    if (it >= warmup)
      lat_ms.push_back(
          std::chrono::duration<double, std::milli>(t1 - t0).count());
  }
  for (int i = 0; i < ptpred_num_fetches(p); i++) {
    printf("fetch %s: shape(", ptpred_fetch_name(p, i));
    for (int d = 0; d < ptpred_out_rank(p, i); d++)
      printf("%s%lld", d ? "," : "", (long long)ptpred_out_dim(p, i, d));
    int64_t nbytes = 0;
    const float* data = (const float*)ptpred_out_data(p, i, &nbytes);
    printf(") first=%g\n", nbytes >= 4 ? data[0] : 0.0);
  }
  if (!lat_ms.empty()) {
    std::sort(lat_ms.begin(), lat_ms.end());
    double sum = 0;
    for (double v : lat_ms) sum += v;
    size_t n = lat_ms.size();
    double p50 = lat_ms[n / 2];
    // nearest-rank percentile: idx = ceil(0.99*n)-1. By definition this
    // still lands on the last sample for any n < 100 — a true p99 needs
    // >= 100 samples (the fill-list ptserve items pass iters=100) — so
    // max is reported as its own field and small-n p99 readings should
    // be read as max, not as a percentile.
    size_t p99_idx = (size_t)std::ceil(0.99 * (double)n);
    double p99 = lat_ms[p99_idx > 0 ? p99_idx - 1 : 0];
    double mx = lat_ms[n - 1];
    double mean = sum / n;
    // one JSON line — the analyzer-latency-test role
    printf(
        "{\"metric\": \"native_serve_latency_ms\", \"p50\": %.3f, "
        "\"p99\": %.3f, \"max\": %.3f, \"mean\": %.3f, \"batch\": %lld, "
        "\"iters\": %zu, \"examples_per_sec\": %.1f}\n",
        p50, p99, mx, mean, (long long)batch, n, batch * 1000.0 / mean);
  }
  ptpred_destroy(p);
  printf("ok\n");
  return 0;
}
