// Native wave packer — C++ twin of kubernetes_simulator_tpu/sim/waves.py
// (pack_waves). Packs pods (arrival order) into fixed-width waves such that
// no pod-group (gang) of at most wave_width members spans waves; a wider gang
// starts on a wave's first slot and fills consecutive waves. Semantics must
// stay bit-identical to the Python fallback (tests/test_native.py pins this).
//
// Part of the framework's native runtime layer: host-side ETL for the
// device scan (SURVEY.md §3.1 "host feeds pod chunks"). At 1M pods the
// Python packer costs ~1.2 s; this is ~30 ms.

#include <cstdint>
#include <cstring>
#include <vector>

extern "C" {

// order:        [n] pod ids in schedule order
// group_of:     [num_pods] group id per pod (-1 = none), indexed by pod id
// wave_width:   W
// out_idx:      [n * W] preallocated, filled with -1-padded waves
// returns       number of waves, or -1 if wave_width <= 0
int64_t ksim_pack_waves(const int32_t* order, int64_t n,
                        const int32_t* group_of, int64_t num_pods,
                        int32_t wave_width, int32_t* out_idx) {
  if (wave_width <= 0) return -1;
  // First pass: group membership lists in schedule order.
  int32_t max_group = -1;
  for (int64_t i = 0; i < n; ++i) {
    int32_t g = group_of[order[i]];
    if (g > max_group) max_group = g;
  }
  std::vector<std::vector<int32_t>> members(
      static_cast<size_t>(max_group + 1));
  for (int64_t i = 0; i < n; ++i) {
    int32_t p = order[i];
    int32_t g = group_of[p];
    if (g >= 0) members[static_cast<size_t>(g)].push_back(p);
  }
  // Second pass: emit waves; a pod pulls its whole gang forward to its
  // first member's position (same as the Python packer's `members[g]`).
  std::vector<uint8_t> consumed(static_cast<size_t>(num_pods), 0);
  int64_t wave = 0;
  int32_t fill = 0;
  int32_t* row = out_idx;
  for (int64_t i = 0; i < wave_width; ++i) row[i] = -1;
  for (int64_t i = 0; i < n; ++i) {
    int32_t p = order[i];
    if (consumed[static_cast<size_t>(p)]) continue;
    int32_t g = group_of[p];
    const int32_t* batch = &p;
    int32_t bsz = 1;
    if (g >= 0) {
      batch = members[static_cast<size_t>(g)].data();
      bsz = static_cast<int32_t>(members[static_cast<size_t>(g)].size());
    }
    if (fill > 0 && fill + bsz > wave_width) {
      // flush
      ++wave;
      row = out_idx + wave * wave_width;
      for (int64_t k = 0; k < wave_width; ++k) row[k] = -1;
      fill = 0;
    }
    for (int32_t k = 0; k < bsz; ++k) {
      if (fill == wave_width) {
        // a gang wider than the wave: on into the next wave
        ++wave;
        row = out_idx + wave * wave_width;
        for (int64_t j = 0; j < wave_width; ++j) row[j] = -1;
        fill = 0;
      }
      row[fill++] = batch[k];
      consumed[static_cast<size_t>(batch[k])] = 1;
    }
  }
  if (fill > 0) ++wave;
  return wave == 0 ? 1 : wave;  // Python packer emits >=1 (possibly all-PAD) row
}

}  // extern "C"
