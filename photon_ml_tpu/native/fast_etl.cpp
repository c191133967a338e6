// Native host-side ETL: the rebuild's C++ runtime for data preparation.
//
// Reference counterpart: the JVM executors' deserialization + shuffle
// machinery (Spark's netty/torrent substrate, SURVEY.md §5.8) and the
// Avro decode path of AvroDataReader [expected reference structure;
// mount unavailable].  The reference leans on the JVM for its data
// plane; the TPU rebuild's data plane is this library + numpy, feeding
// statically-shaped HBM arrays.
//
// Everything here is single-pass, cache-friendly C++ with no
// dependencies beyond the C++17 standard library.  The Python side
// (photon_ml_tpu.native) binds via ctypes and falls back to numpy
// implementations when the shared object is unavailable, so the
// framework never hard-depends on a compiler at runtime.
//
// Exposed surface (extern "C", handle-based two-phase protocol so the
// caller allocates numpy arrays of exactly the right size):
//
//   LIBSVM text  -> CSR-ish (row_ptr, cols, vals, labels)
//   row-ELL      -> transposed-ELL (the colmajor build: counting sort
//                   by column + virtual-row splitting; O(nnz + dim))
//   CSR + entity grouping -> per-entity subspaces and their dense
//                   blocks (the projection of game/projector.py)
//   row-ELL      -> per-column entry counts (pml_column_counts), and
//                   the batch taken apart by column class: hot block,
//                   planned ELL, tail COO (pml_split_classes_sizes /
//                   pml_split_classes_fill; the planner's hot split,
//                   data/grr.py)

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <new>
#include <system_error>
#include <thread>
#include <vector>

namespace {

struct LibsvmResult {
  std::vector<float> labels;
  std::vector<int64_t> row_ptr;  // [n+1]
  std::vector<int32_t> cols;
  std::vector<float> vals;
  int32_t max_col = -1;
};

// Minimal fast float parse: LIBSVM files carry plain decimal floats.
// strtof handles all forms; the win over Python is avoiding per-token
// object allocation, not exotic float parsing.
inline const char* skip_ws(const char* p, const char* end) {
  while (p < end && (*p == ' ' || *p == '\t' || *p == '\r')) ++p;
  return p;
}

}  // namespace

extern "C" {

// ---------------------------------------------------------------------------
// LIBSVM parsing
// ---------------------------------------------------------------------------

void* pml_libsvm_parse(const char* buf, int64_t len) {
  auto* r = new (std::nothrow) LibsvmResult();
  if (!r) return nullptr;
  r->row_ptr.push_back(0);
  const char* p = buf;
  const char* end = buf + len;
  while (p < end) {
    const char* line_end = static_cast<const char*>(
        memchr(p, '\n', static_cast<size_t>(end - p)));
    if (!line_end) line_end = end;
    p = skip_ws(p, line_end);
    if (p < line_end && *p != '#') {
      char* q = nullptr;
      float label = strtof(p, &q);
      if (q == p || q > line_end) {
        delete r;
        return nullptr;
      }
      p = q;
      while (p < line_end) {
        p = skip_ws(p, line_end);
        if (p >= line_end || *p == '#') break;
        long idx = strtol(p, &q, 10);
        if (q == p || q >= line_end || *q != ':') {
          delete r;
          return nullptr;
        }
        p = q + 1;
        float v = strtof(p, &q);
        // strtof may legally run past line_end (the buffer is contiguous
        // across lines), which would silently consume the next line's
        // tokens; a value must both exist and end within its own line.
        if (q == p || q > line_end) {
          delete r;
          return nullptr;
        }
        p = q;
        // Raw file index; 0/1-based conversion happens in Python
        // (vectorized), which also validates the resulting range.
        if (idx < 0 || idx > INT32_MAX) {
          delete r;
          return nullptr;
        }
        int32_t c = static_cast<int32_t>(idx);
        r->cols.push_back(c);
        r->vals.push_back(v);
        if (c > r->max_col) r->max_col = c;
      }
      r->labels.push_back(label);
      r->row_ptr.push_back(static_cast<int64_t>(r->cols.size()));
    }
    p = line_end + 1;
  }
  return r;
}

void pml_libsvm_sizes(void* handle, int64_t* n_rows, int64_t* nnz,
                      int32_t* max_col) {
  auto* r = static_cast<LibsvmResult*>(handle);
  *n_rows = static_cast<int64_t>(r->labels.size());
  *nnz = static_cast<int64_t>(r->cols.size());
  *max_col = r->max_col;
}

void pml_libsvm_fill(void* handle, float* labels, int64_t* row_ptr,
                     int32_t* cols, float* vals) {
  auto* r = static_cast<LibsvmResult*>(handle);
  memcpy(labels, r->labels.data(), r->labels.size() * sizeof(float));
  memcpy(row_ptr, r->row_ptr.data(), r->row_ptr.size() * sizeof(int64_t));
  memcpy(cols, r->cols.data(), r->cols.size() * sizeof(int32_t));
  memcpy(vals, r->vals.data(), r->vals.size() * sizeof(float));
}

void pml_libsvm_free(void* handle) {
  delete static_cast<LibsvmResult*>(handle);
}

// ---------------------------------------------------------------------------
// Transposed-ELL (colmajor) build — see data/colmajor.py for the design.
// Counting sort by column: O(nnz + dim), one read pass + one write pass.
// ---------------------------------------------------------------------------

// Phase 1: count virtual rows for (cols, vals, capacity).  Returns V, or
// -1 on invalid input.  col_counts must be a caller-zeroed [dim] int64
// scratch; it is left holding the per-column nonzero counts for phase 2.
int64_t pml_colmajor_vrows(const int32_t* cols, const float* vals,
                           int64_t n, int64_t k, int64_t dim,
                           int64_t capacity, int64_t* col_counts) {
  const int64_t total = n * k;
  for (int64_t e = 0; e < total; ++e) {
    if (vals[e] != 0.0f) {
      const int32_t c = cols[e];
      if (c < 0 || c >= dim) return -1;
      ++col_counts[c];
    }
  }
  int64_t v = 0;
  for (int64_t j = 0; j < dim; ++j) {
    v += (col_counts[j] + capacity - 1) / capacity;
  }
  return v;
}

// Phase 2: fill caller-allocated tvals [v_pad*capacity] (zeroed),
// trows [v_pad*capacity] (zeroed), vcol [v_pad] (zeroed).  col_counts is
// the phase-1 output.  Entries keep row order within each column
// (counting sort is stable in row-scan order).
void pml_colmajor_fill(const int32_t* cols, const float* vals,
                       int64_t n, int64_t k, int64_t dim,
                       int64_t capacity, const int64_t* col_counts,
                       int64_t v_pad, float* tvals, int32_t* trows,
                       int32_t* vcol) {
  // Per-column virtual-row base and running cursor.
  std::vector<int64_t> vrow_base(static_cast<size_t>(dim) + 1, 0);
  for (int64_t j = 0; j < dim; ++j) {
    vrow_base[static_cast<size_t>(j) + 1] =
        vrow_base[static_cast<size_t>(j)] +
        (col_counts[j] + capacity - 1) / capacity;
  }
  for (int64_t j = 0; j < dim; ++j) {
    const int64_t first = vrow_base[static_cast<size_t>(j)];
    const int64_t nv = vrow_base[static_cast<size_t>(j) + 1] - first;
    for (int64_t t = 0; t < nv; ++t) {
      vcol[first + t] = static_cast<int32_t>(j);
    }
  }
  std::vector<int64_t> cursor(static_cast<size_t>(dim), 0);
  for (int64_t i = 0; i < n; ++i) {
    const int64_t row_off = i * k;
    for (int64_t s = 0; s < k; ++s) {
      const float v = vals[row_off + s];
      if (v == 0.0f) continue;
      const int32_t c = cols[row_off + s];
      const int64_t pos = cursor[c]++;
      const int64_t vr = vrow_base[static_cast<size_t>(c)] + pos / capacity;
      const int64_t slot = pos % capacity;
      tvals[vr * capacity + slot] = v;
      trows[vr * capacity + slot] = static_cast<int32_t>(i);
    }
  }
  (void)v_pad;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Bipartite Euler-split edge coloring — the router for in-tile crossbars.
//
// A static permutation of a [R,128] VMEM tile is executed on TPU as
// lane-perm ∘ transpose ∘ lane-perm ∘ transpose ∘ lane-perm (see
// ops/crossbar.py).  The middle lane-perm is legal iff the edges
// (src_row → dst_row) are properly colored with 128 colors such that no
// two edges at the same vertex share a color.  With every vertex of
// degree exactly n_colors (a power of two; padding slots make this true
// by construction), repeated Euler splitting yields an exact coloring in
// O(m log n_colors): each split walks Euler circuits and alternates
// edges between halves, preserving even degrees.
// ---------------------------------------------------------------------------

namespace {

// One Euler-split level: partition edges[lo:hi) (indices into src/dst)
// into first half = color bit 0, second half = bit 1, by walking Euler
// circuits and alternating.  src[e] in [0,L), dst[e] in [0,R_n).
// Every vertex degree within the subset must be even.
void euler_split_level(const int32_t* src, const int32_t* dst,
                       int64_t* edge_ids, int64_t lo, int64_t hi,
                       int32_t n_left, int32_t n_right,
                       std::vector<int64_t>& head,
                       std::vector<int64_t>& nxt,
                       std::vector<int64_t>& prv,
                       std::vector<uint8_t>& used,
                       std::vector<uint8_t>& side_out) {
  // Build doubly-linked adjacency over vertices 0..n_left-1 (left) and
  // n_left..n_left+n_right-1 (right); each edge appears once per side
  // via two arc slots (2e, 2e+1).
  const int32_t nv = n_left + n_right;
  for (int32_t v = 0; v < nv; ++v) head[v] = -1;
  for (int64_t i = lo; i < hi; ++i) {
    const int64_t e = edge_ids[i];
    used[e] = 0;
    const int64_t a0 = 2 * e, a1 = 2 * e + 1;
    const int32_t u = src[e], w = n_left + dst[e];
    nxt[a0] = head[u]; prv[a0] = -1;
    if (head[u] >= 0) prv[head[u]] = a0;
    head[u] = a0;
    nxt[a1] = head[w]; prv[a1] = -1;
    if (head[w] >= 0) prv[head[w]] = a1;
    head[w] = a1;
  }
  auto detach = [&](int64_t arc, int32_t v) {
    if (prv[arc] >= 0) nxt[prv[arc]] = nxt[arc];
    else head[v] = nxt[arc];
    if (nxt[arc] >= 0) prv[nxt[arc]] = prv[arc];
  };
  // Walk circuits: from any vertex with remaining edges, follow unused
  // edges until returning; alternate sides along the walk.  On a graph
  // with all even degrees the walk can only get stuck at its start
  // vertex, at which point we continue from any still-incident vertex.
  for (int64_t i = lo; i < hi; ++i) {
    const int64_t e0 = edge_ids[i];
    if (used[e0]) continue;
    int32_t v = src[e0];
    uint8_t side = 0;
    while (head[v] >= 0) {
      const int64_t arc = head[v];
      const int64_t e = arc >> 1;
      const int32_t u = src[e], w = n_left + dst[e];
      detach(2 * e, u);
      detach(2 * e + 1, w);
      used[e] = 1;
      side_out[e] = side;
      side ^= 1;
      v = (v == u) ? w : u;
    }
  }
}

// body(k) for every k in [0, n_blocks), on `n_threads` threads at most
// (the caller's included): each takes the next block off a shared
// counter, so blocks that write disjoint memory leave the bytes of one
// serial call.  The
// threads live for this call only (nothing to carry across a fork) and
// never touch Python; a call of one block runs inline.  Returns 0, or
// the first non-zero a body returned (no further block is then begun).
template <class Body>
int32_t for_each_block(int64_t n_blocks, int32_t n_threads, Body body) {
  std::atomic<int64_t> next{0};
  std::atomic<int32_t> rc{0};
  auto work = [&] {
    while (rc.load() == 0) {
      const int64_t k = next.fetch_add(1);
      if (k >= n_blocks) break;
      const int32_t got = body(k);
      if (got != 0) rc.store(got);
    }
  };
  std::vector<std::thread> others;
  const int64_t n_others = std::min<int64_t>(n_threads, n_blocks) - 1;
  try {
    for (int64_t i = 0; i < n_others; ++i) others.emplace_back(work);
  } catch (const std::system_error&) {
    // The process may start no more threads: work on those it got.
  }
  work();
  for (auto& th : others) th.join();
  return rc;
}

}  // namespace

extern "C" {

// Color m edges (src[e] in [0,n_left), dst[e] in [0,n_right)) with
// n_colors colors (power of two).  Every left/right vertex must have
// degree divisible by n_colors... in the crossbar use-case degree ==
// n_colors exactly.  Writes color[e] in [0, n_colors).  Returns 0, or
// -1 on invalid arguments.
int32_t pml_edge_color(const int32_t* src, const int32_t* dst, int64_t m,
                       int32_t n_left, int32_t n_right, int32_t n_colors,
                       int32_t* color) {
  if (n_colors <= 0 || (n_colors & (n_colors - 1)) != 0) return -1;
  if (m < 0 || n_left <= 0 || n_right <= 0) return -1;
  // Vertex-range validation before touching the adjacency arrays: an
  // out-of-range id would index head/nxt/prv out of bounds (heap
  // corruption reachable from Python via edge_color_native).
  for (int64_t e = 0; e < m; ++e) {
    if (src[e] < 0 || src[e] >= n_left || dst[e] < 0 || dst[e] >= n_right)
      return -1;
  }
  std::vector<int64_t> edge_ids(static_cast<size_t>(m));
  for (int64_t e = 0; e < m; ++e) { edge_ids[e] = e; color[e] = 0; }
  std::vector<int64_t> head(static_cast<size_t>(n_left + n_right));
  std::vector<int64_t> nxt(static_cast<size_t>(2 * m));
  std::vector<int64_t> prv(static_cast<size_t>(2 * m));
  std::vector<uint8_t> used(static_cast<size_t>(m));
  std::vector<uint8_t> side(static_cast<size_t>(m));
  std::vector<int64_t> scratch(static_cast<size_t>(m));

  // Iterative halving: ranges of edge_ids sharing a color prefix are
  // split; bit b of the color is assigned at level b (MSB first).
  int32_t levels = 0;
  for (int32_t c = n_colors; c > 1; c >>= 1) ++levels;
  std::vector<std::pair<int64_t, int64_t>> ranges{{0, m}};
  for (int32_t level = 0; level < levels; ++level) {
    std::vector<std::pair<int64_t, int64_t>> next_ranges;
    for (auto [lo, hi] : ranges) {
      if (hi - lo == 0) continue;
      euler_split_level(src, dst, edge_ids.data(), lo, hi, n_left,
                        n_right, head, nxt, prv, used, side);
      // Stable partition: side 0 first.
      int64_t w0 = lo;
      for (int64_t i = lo; i < hi; ++i)
        if (!side[edge_ids[i]]) scratch[w0++] = edge_ids[i];
      int64_t mid = w0;
      for (int64_t i = lo; i < hi; ++i)
        if (side[edge_ids[i]]) scratch[w0++] = edge_ids[i];
      for (int64_t i = lo; i < hi; ++i) edge_ids[i] = scratch[i];
      const int32_t bit = 1 << (levels - 1 - level);
      for (int64_t i = mid; i < hi; ++i) color[edge_ids[i]] |= bit;
      next_ranges.emplace_back(lo, mid);
      next_ranges.emplace_back(mid, hi);
    }
    ranges = std::move(next_ranges);
  }
  return 0;
}

// Batched GRR route builder: for each [128,128] supertile, color the
// start→final slot permutation (dst[t][r*128+l] = final slot of the
// element starting at (r, l)) and emit the three lane-gather stages the
// kernel executes (ops/grr_kernel.py), with route stage 1 pre-composed
// with the gather index plane hi.  This is the hot part of compiling a
// sparse matrix into the GRR plan (data/grr.py) — one Euler-split
// coloring per supertile, O(slots · log 128) each.  Supertiles share
// nothing: this is the serial body over [0, n_st), and
// pml_grr_routes_blocks below runs it on slices from several threads.
// Returns 0, or -1 if any tile's dst is not a bijection / coloring
// arguments are invalid.
int32_t pml_grr_routes(const int32_t* dst, const int8_t* hi, int64_t n_st,
                       int8_t* g1, int8_t* g2, int8_t* g3) {
  constexpr int32_t T = 128;
  constexpr int64_t S = static_cast<int64_t>(T) * T;
  std::vector<int32_t> src_row(S), dst_row(S), color(S);
  std::vector<uint8_t> seen(S);
  for (int64_t e = 0; e < S; ++e) src_row[e] = static_cast<int32_t>(e >> 7);

  for (int64_t t = 0; t < n_st; ++t) {
    const int32_t* d = dst + t * S;
    const int8_t* h = hi + t * S;
    std::memset(seen.data(), 0, static_cast<size_t>(S));
    for (int64_t e = 0; e < S; ++e) {
      const int32_t v = d[e];
      if (v < 0 || v >= S || seen[v]) return -1;
      seen[v] = 1;
      dst_row[e] = v >> 7;
    }
    if (pml_edge_color(src_row.data(), dst_row.data(), S, T, T, T,
                       color.data()) != 0)
      return -1;
    int8_t* G1 = g1 + t * S;
    int8_t* G2 = g2 + t * S;
    int8_t* G3 = g3 + t * S;
    for (int64_t e = 0; e < S; ++e) {
      const int32_t r = src_row[e];
      const int32_t l = static_cast<int32_t>(e & (T - 1));
      const int32_t c = color[e];
      const int32_t dr = dst_row[e];
      const int32_t dl = d[e] & (T - 1);
      G1[r * T + c] = h[r * T + l];
      G2[c * T + dr] = static_cast<int8_t>(r);
      G3[dr * T + dl] = static_cast<int8_t>(c);
    }
  }
  return 0;
}

// pml_grr_routes over contiguous blocks of `block` supertiles on
// `n_threads` threads (for_each_block): each routes its block in
// place, on the pointer offsets of its slice, so the outputs are the
// bytes one serial call writes.  Returns 0, or -1 as soon as any block
// does; the outputs are then unspecified.
int32_t pml_grr_routes_blocks(const int32_t* dst, const int8_t* hi,
                              int64_t n_st, int8_t* g1, int8_t* g2,
                              int8_t* g3, int64_t block,
                              int32_t n_threads) {
  constexpr int64_t S = 128 * 128;
  if (block < 1) return -1;
  return for_each_block(
      (n_st + block - 1) / block, n_threads, [&](int64_t k) {
        const int64_t t0 = k * block;
        return pml_grr_routes(dst + t0 * S, hi + t0 * S,
                              std::min(block, n_st - t0), g1 + t0 * S,
                              g2 + t0 * S, g3 + t0 * S);
      });
}

}  // extern "C"

// ---------------------------------------------------------------------------
// GRR plan construction (the layout half of the sparse engine)
// ---------------------------------------------------------------------------
//
// Builds one direction's gather-route-reduce plan from its entries, in
// the order the caller gives them: the cells of a row-ELL array
// (pml_grr_plan) or a COO triple (pml_grr_plan_coo, the spill of the
// level above or a mid split).  The same pipeline as
// photon_ml_tpu.data.grr's numpy body (group-capacity ranks, supertile
// blocking, start/final slot placement, padding bijection, spill COO),
// but as a handful of streaming passes over the entries with small
// cache-local counter tables — no 10^8-element comparison sorts, no
// full-size temporaries.  A rank within a group follows entry scan
// order, and so does the numpy body's (its argsort is stable): at one
// cap the two builders write the same bytes, leaf for leaf (tested in
// tests/test_grr.py).  They differ only in how an absent cap is
// resolved: pml_grr_plan takes the exact mean occupancy, the numpy
// heuristic a sample of segments; pml_grr_plan_coo is always given the
// cap.
//
// Protocol, split at the sizes so that the slot arrays are the caller's:
//   pml_grr_plan(...) or pml_grr_plan_coo(...) -> handle: the serial
//     head (grr_plan_head): pass A, the cap, pass B's counts and the
//     block list, and no slot array;
//   pml_grr_plan_sizes(handle, ...): the sizes, or why there is no plan;
//   pml_grr_plan_fill(handle, ..., n_threads): passes C and D straight
//     into the caller's arrays (grr_plan_fill), then the spill;
//   pml_grr_plan_free(handle).
// The handle keeps pointers to the entries: they must outlive the fill.
// Route coloring stays in pml_grr_routes (shared with the Python path).
//
// The fill takes one output window a block, on for_each_block's
// threads.  Window ow holds the segments [ow * segwin, (ow + 1) *
// segwin): the rank counters its entries touch (q per (segment, table
// window), the start-lane rank per (block, sub-tile row)) and its
// supertiles (a run of them: the block list is ow-major) are no other
// window's, and its entries keep their scan order, so every rank, slot
// and spill decision is the serial pass's.  The spill is written last,
// in scan order over every entry, from a byte mask the windows mark:
// on chunks of cells, each from the count of the chunks before it.

namespace {

constexpr int64_t GRR_WIN = 16384;
constexpr int32_t GRR_TILE = 128;
constexpr int64_t GRR_SLOTS = GRR_WIN;  // 128*128 slots per supertile

inline int32_t grr_next_pow2(int64_t x) {
  // Callers clamp the result to <= 64; clamp the input too so an
  // extreme occupancy mean can't overflow the int32 shift (UB).
  if (x > 128) x = 128;
  int32_t p = 1;
  while (p < x) p <<= 1;
  return p;
}

// The two entry sources of a plan: `size` cells, some of value zero,
// cell e holding the entry (idx, seg) = at(e).  first_cell(seg): where
// a source holds its entries in segment order, one run of cells a
// segment (an ELL array whose rows are the segments), the first cell
// of segment seg's run; -1 where it does not.
struct EllEntries {  // cell (r, j) of [n, k] cols/vals: row r, column c
  const int32_t* cols;
  const float* vals;
  int64_t k, size;
  int32_t direction;  // 0: idx = column, seg = row; 1: the transpose
  float val(int64_t e) const { return vals[e]; }
  void at(int64_t e, int64_t* idx, int64_t* seg) const {
    const int64_t r = e / k;
    const int64_t c = cols[e];
    *idx = direction ? r : c;
    *seg = direction ? c : r;
  }
  int64_t first_cell(int64_t seg) const { return direction ? -1 : seg * k; }
};

struct CooEntries {
  const int32_t* idx_of;
  const int32_t* seg_of;
  const float* vals;
  int64_t size;
  float val(int64_t e) const { return vals[e]; }
  void at(int64_t e, int64_t* idx, int64_t* seg) const {
    *idx = idx_of[e];
    *seg = seg_of[e];
  }
  int64_t first_cell(int64_t) const { return -1; }
};

struct GrrPlan {
  // 1 = idx/seg out of range, 2 = size overflow, 3 = bad argument,
  // 4 = more supertiles than the caller's bound (sizes only, no arrays)
  int32_t error = 0;
  int32_t cap = 0, n_gw = 0, n_ow = 0;
  int64_t n_st = 0, n_spill = 0;  // n_spill already padded to 8
  std::vector<int32_t> gw_of_st, ow_of_st, first_of_ow;
  // What the fill takes from the head: the entries (ell, or coo where
  // is_coo), the table range, whether the (seg, window) keys arrive
  // sorted, the spilled entries' count (m_spill, before padding), the
  // supertile of every block, the first supertile of every output
  // window ([n_ow + 1]) and the counter tables, which each window of
  // the fill zeroes for itself.
  EllEntries ell{};
  CooEntries coo{};
  bool is_coo = false, sorted = true;
  int64_t n_segments = 0, idx_lo = 0, idx_hi = 0, m_spill = 0;
  std::vector<int32_t> st_of_bk, st_of_ow;
  std::vector<uint8_t> qcnt;
  std::vector<uint16_t> r2cnt;
};

// The head behind an exception firewall: std::bad_alloc must not
// unwind through the extern "C"/ctypes boundary (that would terminate
// the process instead of letting the caller fall back to numpy).
//
// [idx_lo, idx_hi) restricts the plan to a contiguous sub-range of the
// table axis (the column-range split of data/grr.py): entries outside
// the range are SKIPPED (they belong to a sibling sub-plan — not spill,
// not an error), in-range indices are rebased to idx - idx_lo, and the
// emitted plan's table axis is [0, idx_hi - idx_lo).  Indices outside
// [0, table_len) are still a hard error — every entry belongs to
// exactly one range of a full partition, so a genuinely out-of-range
// id must not be silently dropped by all parts.
//
// max_st >= 0 bounds the supertiles the caller will pay for (the
// overflow chain's economy test, data/grr.py _spill_overflow): a plan
// with more stops once they are counted, with error 4 and its sizes.
template <class Entries>
void grr_plan_head(GrrPlan* plan, const Entries& in, int64_t table_len,
                   int64_t n_segments, int32_t cap_in, int64_t idx_lo,
                   int64_t idx_hi, int64_t max_st) {
  // Same cap validation as the numpy path (data/grr.py): a non-power-
  // of-two cap makes distinct (q, b) pairs collide on one final slot.
  if (cap_in != 0 && cap_in != 1 && cap_in != 2 && cap_in != 4 &&
      cap_in != 8 && cap_in != 16 && cap_in != 32 && cap_in != 64 &&
      cap_in != 128) {
    plan->error = 3;
    return;
  }
  constexpr int64_t kMaxCounterBytes = int64_t{1} << 33;  // 8 GB
  if (idx_hi <= 0) idx_hi = table_len;
  if (idx_lo < 0 || idx_hi > table_len || idx_lo >= idx_hi ||
      (idx_lo % GRR_WIN) != 0) {
    plan->error = 3;
    return;
  }
  const int64_t range_len = idx_hi - idx_lo;
  const int64_t n_gw = (range_len + GRR_WIN - 1) / GRR_WIN;
  plan->n_gw = static_cast<int32_t>(n_gw);
  const int64_t m_ell = in.size;

  // Pass A: count nonzeros, validate ranges, check (seg, gw) sortedness.
  int64_t m_nz = 0;
  bool sorted = true;
  int64_t prev_key = -1;
  for (int64_t e = 0; e < m_ell; ++e) {
    if (in.val(e) == 0.0f) continue;
    int64_t idx, seg;
    in.at(e, &idx, &seg);
    if (idx < 0 || idx >= table_len || seg < 0 || seg >= n_segments) {
      plan->error = 1;
      return;
    }
    if (idx < idx_lo || idx >= idx_hi) continue;
    idx -= idx_lo;
    const int64_t key = seg * n_gw + idx / GRR_WIN;
    if (key < prev_key) sorted = false;
    prev_key = key;
    ++m_nz;
  }

  // Capacity: 1.5x the exact mean nonempty (seg, window) occupancy
  // (the Python path estimates this mean by sampling segments; exact
  // is strictly better and free here).
  int32_t cap = cap_in;
  if (cap <= 0) {
    int64_t n_groups = 0;
    if (sorted) {
      prev_key = -1;
      for (int64_t e = 0; e < m_ell; ++e) {
        if (in.val(e) == 0.0f) continue;
        int64_t idx, seg;
        in.at(e, &idx, &seg);
        if (idx < idx_lo || idx >= idx_hi) continue;
        const int64_t key = seg * n_gw + (idx - idx_lo) / GRR_WIN;
        if (key != prev_key) ++n_groups;
        prev_key = key;
      }
    } else {
      const int64_t n_keys = n_segments * n_gw;
      if (n_keys > kMaxCounterBytes) {
        plan->error = 2;
        return;
      }
      std::vector<uint8_t> visited(static_cast<size_t>(n_keys), 0);
      for (int64_t e = 0; e < m_ell; ++e) {
        if (in.val(e) == 0.0f) continue;
        int64_t idx, seg;
        in.at(e, &idx, &seg);
        if (idx < idx_lo || idx >= idx_hi) continue;
        const int64_t key = seg * n_gw + (idx - idx_lo) / GRR_WIN;
        if (!visited[key]) { visited[key] = 1; ++n_groups; }
      }
    }
    const double mean = n_groups ? double(m_nz) / double(n_groups) : 1.0;
    cap = grr_next_pow2(static_cast<int64_t>(mean * 1.5 + 0.999999));
    if (cap < 4) cap = 4;
    if (cap > 64) cap = 64;
  }
  plan->cap = cap;
  const int64_t segwin = GRR_WIN / cap;
  const int64_t n_ow = n_segments > 0 ? (n_segments + segwin - 1) / segwin : 1;
  plan->n_ow = static_cast<int32_t>(n_ow);
  const int64_t n_bk = n_ow * n_gw;
  if (n_bk * GRR_TILE * 2 > kMaxCounterBytes) {  // r2cnt bytes
    plan->error = 2;
    return;
  }

  // Rank counters.  q: per (seg, window) among all entries (uint8,
  // cap <= 64 < 255 so saturate at 255 = spilled anyway).  rank2: per
  // (block, sub-tile row) among cap-kept entries.
  std::vector<uint8_t>& qcnt = plan->qcnt;
  if (!sorted) {
    if (n_segments * n_gw > kMaxCounterBytes) {
      plan->error = 2;
      return;
    }
    qcnt.assign(static_cast<size_t>(n_segments * n_gw), 0);
  }
  std::vector<uint16_t>& r2cnt = plan->r2cnt;
  r2cnt.assign(static_cast<size_t>(n_bk) * GRR_TILE, 0);
  std::vector<int64_t> cnt_bk(static_cast<size_t>(n_bk), 0);

  // Pass B: count kept entries per block, ranked as pass C ranks them
  // (no fills), so the spill's length is known before any slot is.
  {
    int64_t run_key = -1, run_q = 0;
    for (int64_t e = 0; e < m_ell; ++e) {
      const float v = in.val(e);
      if (v == 0.0f) continue;
      int64_t idx, seg;
      in.at(e, &idx, &seg);
      if (idx < idx_lo || idx >= idx_hi) continue;
      idx -= idx_lo;
      const int64_t gw = idx / GRR_WIN;
      int64_t q;
      if (sorted) {
        const int64_t key = seg * n_gw + gw;
        if (key != run_key) { run_key = key; run_q = 0; }
        q = run_q++;
      } else {
        uint8_t& qc = qcnt[seg * n_gw + gw];
        q = qc;
        if (qc < 255) ++qc;
      }
      if (q >= cap) continue;  // spill1
      const int64_t bk = (seg / segwin) * n_gw + gw;
      // A block holds at most segwin * cap = 16384 cap-kept entries,
      // so no counter wraps.
      uint16_t& r2 = r2cnt[bk * GRR_TILE + (idx % GRR_WIN) / GRR_TILE];
      if (r2 >= GRR_TILE) { ++r2; continue; }  // spill2 (sat. anyway)
      ++r2;
      ++cnt_bk[bk];
    }
  }

  // Block list: non-empty blocks ascending + a dummy per empty ow.
  int64_t m_kept = 0;
  for (int64_t b = 0; b < n_bk; ++b) m_kept += cnt_bk[b];
  plan->st_of_bk.assign(static_cast<size_t>(n_bk), -1);
  plan->st_of_ow.resize(static_cast<size_t>(n_ow + 1));
  {
    std::vector<uint8_t> ow_present(static_cast<size_t>(n_ow), 0);
    for (int64_t b = 0; b < n_bk; ++b)
      if (cnt_bk[b] > 0) ow_present[b / n_gw] = 1;
    int64_t n_st = 0;
    for (int64_t ow = 0; ow < n_ow; ++ow) {
      if (ow_present[ow]) {
        for (int64_t g = 0; g < n_gw; ++g)
          if (cnt_bk[ow * n_gw + g] > 0) ++n_st;
      } else {
        ++n_st;  // dummy at (ow, gw=0)
      }
    }
    plan->n_st = n_st;
    if (max_st >= 0 && n_st > max_st) {
      plan->error = 4;
      return;
    }
    plan->gw_of_st.resize(static_cast<size_t>(n_st));
    plan->ow_of_st.resize(static_cast<size_t>(n_st));
    plan->first_of_ow.resize(static_cast<size_t>(n_st));
    int32_t st = 0;
    for (int64_t ow = 0; ow < n_ow; ++ow) {
      plan->st_of_ow[ow] = st;
      if (ow_present[ow]) {
        for (int64_t g = 0; g < n_gw; ++g) {
          const int64_t b = ow * n_gw + g;
          if (cnt_bk[b] <= 0) continue;
          plan->st_of_bk[b] = st;
          plan->gw_of_st[st] = static_cast<int32_t>(g);
          plan->ow_of_st[st] = static_cast<int32_t>(ow);
          plan->first_of_ow[st] = st == plan->st_of_ow[ow] ? 1 : 0;
          ++st;
        }
      } else {
        plan->gw_of_st[st] = 0;
        plan->ow_of_st[st] = static_cast<int32_t>(ow);
        plan->first_of_ow[st] = 1;
        ++st;
      }
    }
    plan->st_of_ow[n_ow] = st;
  }
  plan->sorted = sorted;
  plan->n_segments = n_segments;
  plan->idx_lo = idx_lo;
  plan->idx_hi = idx_hi;
  plan->m_spill = m_nz - m_kept;
  plan->n_spill = plan->m_spill ? ((plan->m_spill + 7) / 8) * 8 : 0;
}

// Passes C and D into the caller's arrays (hi, vals, dst: [n_st, 16384];
// the spill's three: [n_spill]), one output window a block on
// `n_threads` threads at most, then the spill.  Every element of every
// output is written.  Returns 0; -1 where the spill disagrees with the
// head's count (the outputs are then unspecified); -2: out of memory.
template <class Entries>
int32_t grr_plan_fill(GrrPlan* p, const Entries& in, int8_t* hi,
                      float* vals, int32_t* dst, int32_t* spill_idx,
                      int32_t* spill_seg, float* spill_val,
                      int32_t n_threads) {
  const int64_t n_gw = p->n_gw, n_ow = p->n_ow, cap = p->cap;
  const int64_t segwin = GRR_WIN / cap;
  const int64_t group = GRR_TILE / cap;
  const int64_t m_ell = in.size;
  // The entry (idx rebased, seg) of cell e, or false where the cell
  // holds none of this plan's (a zero, or an idx outside the range).
  auto entry = [&](int64_t e, int64_t* idx, int64_t* seg) -> bool {
    if (in.val(e) == 0.0f) return false;
    in.at(e, idx, seg);
    if (*idx < p->idx_lo || *idx >= p->idx_hi) return false;
    *idx -= p->idx_lo;
    return true;
  };

  // The passes over every cell run on chunks of cells, a few a thread.
  const int64_t n_chunks = 4 * std::max<int64_t>(n_threads, 1);
  const int64_t chunk = (m_ell + n_chunks - 1) / n_chunks;
  auto over_chunks = [&](auto body) {  // body(c, e0, e1)
    for_each_block(n_chunks, n_threads, [&](int64_t c) {
      body(c, std::min(m_ell, c * chunk), std::min(m_ell, (c + 1) * chunk));
      return 0;
    });
  };

  // Window ow's cells are [first[ow], first[ow + 1]): of the entries
  // themselves where the source keeps its segments in order (or there
  // is one window), else of `order`, the cells sorted by window: a
  // counting sort over the chunks, which keeps the scan order within
  // each window.
  std::vector<int64_t> first(static_cast<size_t>(n_ow + 1)), order;
  const bool runs = n_ow == 1 || in.first_cell(0) >= 0;
  if (runs) {
    first[0] = 0;
    for (int64_t ow = 1; ow < n_ow; ++ow)
      first[ow] = std::min(in.first_cell(ow * segwin), m_ell);
    first[n_ow] = m_ell;
  } else {
    // place[c * n_ow + ow]: chunk c's entries of window ow; then where
    // the next of them goes in `order`.
    std::vector<int64_t> place(static_cast<size_t>(n_chunks * n_ow), 0);
    over_chunks([&](int64_t c, int64_t e0, int64_t e1) {
      int64_t* mine = place.data() + c * n_ow;
      int64_t idx, seg;
      for (int64_t e = e0; e < e1; ++e)
        if (entry(e, &idx, &seg)) ++mine[seg / segwin];
    });
    int64_t t = 0;
    for (int64_t ow = 0; ow < n_ow; ++ow) {
      first[ow] = t;
      for (int64_t c = 0; c < n_chunks; ++c) {
        const int64_t n = place[c * n_ow + ow];
        place[c * n_ow + ow] = t;
        t += n;
      }
    }
    first[n_ow] = t;
    order.resize(static_cast<size_t>(t));
    over_chunks([&](int64_t c, int64_t e0, int64_t e1) {
      int64_t* next = place.data() + c * n_ow;
      int64_t idx, seg;
      for (int64_t e = e0; e < e1; ++e)
        if (entry(e, &idx, &seg)) order[next[seg / segwin]++] = e;
    });
  }
  const int64_t* by_window = runs ? nullptr : order.data();
  std::vector<uint8_t> spilled(static_cast<size_t>(m_ell), 0);

  const int32_t rc = for_each_block(n_ow, n_threads, [&](int64_t ow) {
    const int64_t st0 = p->st_of_ow[ow], n_mine = p->st_of_ow[ow + 1] - st0;
    const int64_t seg0 = ow * segwin;
    const int64_t seg1 = std::min(seg0 + segwin, p->n_segments);
    // The window's slots and counters, zeroed here: their pages are
    // first touched by the thread that fills them.
    std::memset(hi + st0 * GRR_SLOTS, 0, n_mine * GRR_SLOTS);
    std::memset(vals + st0 * GRR_SLOTS, 0, n_mine * GRR_SLOTS * sizeof(float));
    if (!p->sorted && seg1 > seg0)
      std::memset(p->qcnt.data() + seg0 * n_gw, 0, (seg1 - seg0) * n_gw);
    std::memset(p->r2cnt.data() + ow * n_gw * GRR_TILE, 0,
                n_gw * GRR_TILE * sizeof(uint16_t));
    std::vector<uint64_t> occ_s, occ_f;  // the window's occupancy bitmaps
    try {
      occ_s.assign(static_cast<size_t>(n_mine) * (GRR_SLOTS / 64), 0);
      occ_f.assign(static_cast<size_t>(n_mine) * (GRR_SLOTS / 64), 0);
    } catch (const std::bad_alloc&) {
      return -2;
    }

    // Pass C: fill HI/VALS/DST + occupancy bitmaps; mark the spill.
    int64_t run_key = -1, run_q = 0;
    for (int64_t j = first[ow]; j < first[ow + 1]; ++j) {
      const int64_t e = by_window ? by_window[j] : j;
      int64_t idx, seg;
      if (!entry(e, &idx, &seg)) continue;
      const int64_t gw = idx / GRR_WIN;
      int64_t q;
      if (p->sorted) {
        const int64_t key = seg * n_gw + gw;
        if (key != run_key) { run_key = key; run_q = 0; }
        q = run_q++;
      } else {
        uint8_t& qc = p->qcnt[seg * n_gw + gw];
        q = qc;
        if (qc < 255) ++qc;
      }
      bool spill = q >= cap;
      int64_t l_s = 0;
      const int64_t bk = ow * n_gw + gw;
      // Start ROW = the entry's window sub-tile (idx%WIN)/128, so the
      // kernel gathers from the UNtransposed table window: row s of the
      // window holds table[gw*WIN + s*128 .. +127] and the gather plane
      // carries the lane residue idx%128.
      const int64_t hrow = (idx % GRR_WIN) / GRR_TILE;
      if (!spill) {
        uint16_t& r2 = p->r2cnt[bk * GRR_TILE + hrow];
        l_s = r2;
        ++r2;
        spill = l_s >= GRR_TILE;
      }
      if (spill) {
        spilled[e] = 1;
        continue;
      }
      const int64_t st = p->st_of_bk[bk];
      const int64_t b = seg % segwin;
      const int64_t s_start = hrow * GRR_TILE + l_s;
      const int64_t s_final =
          (q * group + b / GRR_TILE) * GRR_TILE + (b % GRR_TILE);
      const int64_t base = st * GRR_SLOTS, mine = (st - st0) * GRR_SLOTS;
      hi[base + s_start] = static_cast<int8_t>(idx % GRR_TILE);
      vals[base + s_final] = in.val(e);
      dst[base + s_start] = static_cast<int32_t>(s_final);
      occ_s[(mine + s_start) >> 6] |= (uint64_t{1} << (s_start & 63));
      occ_f[(mine + s_final) >> 6] |= (uint64_t{1} << (s_final & 63));
    }

    // Pass D: padding bijection — pair free starts with free finals in
    // order (same construction as the Python path).
    for (int64_t t = 0; t < n_mine; ++t) {
      const int64_t base = (st0 + t) * GRR_SLOTS, mine = t * GRR_SLOTS;
      int64_t f = 0;  // next candidate free final
      for (int64_t s = 0; s < GRR_SLOTS; ++s) {
        if (occ_s[(mine + s) >> 6] & (uint64_t{1} << (s & 63))) continue;
        while (f < GRR_SLOTS &&
               (occ_f[(mine + f) >> 6] & (uint64_t{1} << (f & 63))))
          ++f;
        dst[base + s] = static_cast<int32_t>(f);
        ++f;
      }
    }
    return 0;
  });
  if (rc != 0) return rc;

  // The spill COO in scan order, padded to a multiple of 8: each chunk
  // counts its marked cells, then writes them from where the chunks
  // before it end.
  std::vector<int64_t> spill_at(static_cast<size_t>(n_chunks + 1), 0);
  over_chunks([&](int64_t c, int64_t e0, int64_t e1) {
    int64_t n = 0;
    for (int64_t e = e0; e < e1; ++e) n += spilled[e];
    spill_at[c + 1] = n;
  });
  for (int64_t c = 0; c < n_chunks; ++c) spill_at[c + 1] += spill_at[c];
  if (spill_at[n_chunks] != p->m_spill) return -1;
  over_chunks([&](int64_t c, int64_t e0, int64_t e1) {
    int64_t t = spill_at[c], idx = 0, seg = 0;
    for (int64_t e = e0; e < e1; ++e) {
      if (!spilled[e]) continue;
      entry(e, &idx, &seg);
      spill_idx[t] = static_cast<int32_t>(idx);
      spill_seg[t] = static_cast<int32_t>(seg);
      spill_val[t] = in.val(e);
      ++t;
    }
  });
  for (int64_t t = p->m_spill; t < p->n_spill; ++t) {
    spill_idx[t] = spill_seg[t] = 0;
    spill_val[t] = 0.0f;
  }
  return 0;
}

}  // namespace

extern "C" {

void* pml_grr_plan(const int32_t* cols, const float* vals, int64_t n,
                   int64_t k, int32_t direction, int64_t table_len,
                   int64_t n_segments, int32_t cap_in, int64_t idx_lo,
                   int64_t idx_hi) {
  auto* plan = new (std::nothrow) GrrPlan();
  if (!plan) return nullptr;
  plan->ell = EllEntries{cols, vals, k, n * k, direction};
  try {
    grr_plan_head(plan, plan->ell, table_len, n_segments, cap_in, idx_lo,
                  idx_hi, -1);
  } catch (const std::bad_alloc&) {
    plan->error = 2;  // caller falls back to the numpy path
  }
  return plan;
}

// The same plan from m COO entries (idx[e], seg[e], val[e]) in the
// order given, over the whole table axis.  `cap` is required (the
// caller resolves it, data/grr.py); max_st < 0: no bound (see
// grr_plan_head).  Touches nothing shared: any number of threads may
// call it, and the fill, at once.
void* pml_grr_plan_coo(const int32_t* idx, const int32_t* seg,
                       const float* val, int64_t m, int64_t table_len,
                       int64_t n_segments, int32_t cap, int64_t max_st) {
  auto* plan = new (std::nothrow) GrrPlan();
  if (!plan) return nullptr;
  if (cap <= 0) {
    plan->error = 3;
    return plan;
  }
  plan->is_coo = true;
  plan->coo = CooEntries{idx, seg, val, m};
  try {
    grr_plan_head(plan, plan->coo, table_len, n_segments, cap, 0, 0,
                  max_st);
  } catch (const std::bad_alloc&) {
    plan->error = 2;  // caller falls back to the numpy path
  }
  return plan;
}

void pml_grr_plan_sizes(void* handle, int64_t* n_st, int64_t* n_spill,
                        int32_t* cap, int32_t* n_gw, int32_t* n_ow,
                        int32_t* error) {
  auto* p = static_cast<GrrPlan*>(handle);
  *n_st = p->n_st;
  *n_spill = p->n_spill;
  *cap = p->cap;
  *n_gw = p->n_gw;
  *n_ow = p->n_ow;
  *error = p->error;
}

// The plan of a handle whose sizes said no error, into the caller's
// arrays: hi int8, vals float32, dst int32, each [n_st, 128, 128] and
// needing no initial value; the block maps [n_st]; the spill's three
// [n_spill].  On `n_threads` threads at most, the caller's included
// (grr_plan_fill).  Returns 0; -1: a handle with an error, or an
// internal disagreement; -2: out of memory (the caller's numpy body
// decides).
int32_t pml_grr_plan_fill(void* handle, int8_t* hi, float* vals,
                          int32_t* dst, int32_t* gw_of_st, int32_t* ow_of_st,
                          int32_t* first_of_ow, int32_t* spill_idx,
                          int32_t* spill_seg, float* spill_val,
                          int32_t n_threads) {
  auto* p = static_cast<GrrPlan*>(handle);
  if (p->error != 0) return -1;
  const size_t n_st = static_cast<size_t>(p->n_st);
  std::memcpy(gw_of_st, p->gw_of_st.data(), n_st * sizeof(int32_t));
  std::memcpy(ow_of_st, p->ow_of_st.data(), n_st * sizeof(int32_t));
  std::memcpy(first_of_ow, p->first_of_ow.data(), n_st * sizeof(int32_t));
  try {
    return p->is_coo
               ? grr_plan_fill(p, p->coo, hi, vals, dst, spill_idx,
                               spill_seg, spill_val, n_threads)
               : grr_plan_fill(p, p->ell, hi, vals, dst, spill_idx,
                               spill_seg, spill_val, n_threads);
  } catch (const std::bad_alloc&) {
    return -2;
  }
}

void pml_grr_plan_free(void* handle) { delete static_cast<GrrPlan*>(handle); }

}  // extern "C"

// ---------------------------------------------------------------------------
// The planner's hot split (data/grr.py _build_pair_cold): the two passes
// over every entry of an ELL batch that come before any plan is built.
// ---------------------------------------------------------------------------
//
// Rows share nothing but the count table, so both run on row blocks
// over for_each_block's threads and leave the bytes of the numpy bodies
// (np.bincount; _split_classes): integer sums commute, and every other
// output is written at a place its row alone decides.  Nothing of the
// size of the batch or of `dim` is allocated here: the outputs are the
// caller's, zeroed where said and otherwise untouched, so their pages
// are first touched by the threads that fill them.

namespace {

constexpr int32_t kTailCode = INT32_MIN;  // data/grr.py _TAIL_CODE

// Slots of a block's private count cache, direct-mapped: 32 KB.  A
// third of a one-hot batch's entries fall on a few dozen columns (the
// small fields, the intercept): bare atomic increments from every
// thread meet on those cache lines, so a block counts in private and
// adds a slot to the shared table when another column takes it.
constexpr int32_t kCountCacheBits = 12;

}  // namespace

extern "C" {

// counts[c] += the entries of cols[0:m] equal to c whose value is not
// zero.  counts: [dim] int64, zeroed by the caller.  Entries of value
// zero (ELL padding) are not looked at.  Returns 0, or -1 at a column
// outside [0, dim) (counts is then unspecified; nothing is written
// outside it).
int32_t pml_column_counts(const int32_t* cols, const float* vals, int64_t m,
                          int64_t dim, int64_t* counts, int64_t block,
                          int32_t n_threads) {
  // a slot counts in int32: a block holds fewer entries than that
  if (m < 0 || dim < 0 || block < 1 || block > (int64_t{1} << 30)) return -1;
  constexpr int64_t kSlots = int64_t{1} << kCountCacheBits;
  return for_each_block((m + block - 1) / block, n_threads, [&](int64_t b) {
    int32_t key[kSlots], held[kSlots];
    std::fill(key, key + kSlots, -1);
    auto flush = [&](int64_t s) {
      __atomic_fetch_add(&counts[key[s]], int64_t{held[s]}, __ATOMIC_RELAXED);
    };
    const int64_t e1 = std::min(m, (b + 1) * block);
    for (int64_t e = b * block; e < e1; ++e) {
      if (vals[e] == 0.0f) continue;
      const int32_t c = cols[e];
      if (c < 0 || c >= dim) return -1;
      // multiplicative hash: ids a power of two apart share no slot
      const uint32_t s =
          (static_cast<uint32_t>(c) * 2654435761u) >> (32 - kCountCacheBits);
      if (key[s] == c) {
        ++held[s];
        continue;
      }
      if (key[s] >= 0) flush(s);
      key[s] = c;
      held[s] = 1;
    }
    for (int64_t s = 0; s < kSlots; ++s)
      if (key[s] >= 0) flush(s);
    return 0;
  });
}

// First call of the class split.  cols/vals: the [n, k] ELL batch;
// code [dim]: every column's class (a planned column: its id in the
// plans, >= 0; a hot column: -1 - its rank among the hot; a tail
// column: INT32_MIN).  Writes tail_start [n_blocks + 1], n_blocks =
// ceil(n / block_rows): block b's tail entries are
// [tail_start[b], tail_start[b + 1]) of the tail's arrays.  Returns 0,
// or -1 at a column outside [0, dim), padding entries included (the
// second call reads every entry's code).
int32_t pml_split_classes_sizes(const int32_t* cols, const float* vals,
                                int64_t n, int64_t k, const int32_t* code,
                                int64_t dim, int64_t block_rows,
                                int32_t n_threads, int64_t* tail_start) {
  if (n < 0 || k < 0 || dim < 0 || block_rows < 1) return -1;
  const int64_t n_blocks = (n + block_rows - 1) / block_rows;
  tail_start[0] = 0;
  const int32_t rc = for_each_block(n_blocks, n_threads, [&](int64_t b) {
    const int64_t e1 = std::min(n, (b + 1) * block_rows) * k;
    int64_t in_tail = 0;
    for (int64_t e = b * block_rows * k; e < e1; ++e) {
      const int32_t c = cols[e];
      if (c < 0 || c >= dim) return -1;
      in_tail += vals[e] != 0.0f && code[c] == kTailCode;
    }
    tail_start[b + 1] = in_tail;
    return 0;
  });
  if (rc != 0) return rc;
  for (int64_t b = 0; b < n_blocks; ++b) tail_start[b + 1] += tail_start[b];
  return 0;
}

// Second call, with the first call's tail_start and the same blocks.
// x_hot: [n, n_hot] float32, zeroed: a row's hot entries added in entry
// order.  cols_out: [n, k] int32, max(code, 0) of every entry, or null
// (the plans keep the batch's own column ids).  vals_out: [n, k]
// float32, the value of an entry of a planned column and 0.0 of every
// other.  tail_row / tail_col / tail_val: [tail_start[n_blocks]], the
// tail's entries in row order, then entry order.  cols_out, vals_out
// and the tail's three need no initial value: every element is written.
// Returns 0, or -1 at a hot rank outside [0, n_hot) (the outputs are
// then unspecified; nothing is written outside them).  The columns
// were checked by the first call.
int32_t pml_split_classes_fill(const int32_t* cols, const float* vals,
                               int64_t n, int64_t k, const int32_t* code,
                               int64_t n_hot, int64_t block_rows,
                               int32_t n_threads, const int64_t* tail_start,
                               float* x_hot, int32_t* cols_out,
                               float* vals_out, int32_t* tail_row,
                               int32_t* tail_col, float* tail_val) {
  if (n < 0 || k < 0 || n_hot < 0 || block_rows < 1) return -1;
  const int64_t n_blocks = (n + block_rows - 1) / block_rows;
  return for_each_block(n_blocks, n_threads, [&](int64_t b) {
    const int64_t r1 = std::min(n, (b + 1) * block_rows);
    int64_t t = tail_start[b];
    for (int64_t r = b * block_rows; r < r1; ++r) {
      float* hot_row = x_hot + r * n_hot;
      for (int64_t e = r * k; e < (r + 1) * k; ++e) {
        const int32_t c = cols[e];
        const int32_t cls = code[c];
        const float v = vals[e];
        if (cols_out) cols_out[e] = cls > 0 ? cls : 0;
        vals_out[e] = (cls >= 0 && v != 0.0f) ? v : 0.0f;
        if (cls >= 0 || v == 0.0f) continue;
        if (cls == kTailCode) {
          if (t >= tail_start[b + 1]) return -1;
          tail_row[t] = static_cast<int32_t>(r);
          tail_col[t] = c;
          tail_val[t] = v;
          ++t;
        } else {
          const int64_t h = -1 - int64_t{cls};
          if (h >= n_hot) return -1;
          hot_row[h] += v;
        }
      }
    }
    return 0;
  });
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Per-entity subspace projection (game/projector.py): a random effect's
// sparse shard into dense per-bucket blocks, each entity in the
// subspace of the columns it saw.
// ---------------------------------------------------------------------------
//
// Entities are numbered by RANK, their place in (bucket, slot) order,
// and share nothing: an entity's subspace is the distinct columns of
// its examples' entries, ascending, and a column's local index is its
// place there -- what one sort of every entry by (rank, column) yields
// in the numpy body, here a sort of one entity's entries at a time.
// The outputs are that body's bytes (tests/test_native.py).
//
// Two calls, because a bucket's blocks are as wide as its widest
// subspace: pml_re_project_widths lists every entity's examples and
// counts its distinct columns; the caller allocates feature_ids[b]
// (-1) and x_blocks[b] (zeros) for every bucket; pml_re_project_fill
// writes each entity's row and slab in place.  Both share the entities
// out in blocks of about `block` entries (re_block_ranks) over
// for_each_block's threads, the widest entities first, so the first
// touches of the blocks' pages happen on every core.

namespace {

// The ranks [r0, r1) of the k-th block taken, of n_blocks: counted
// from the last rank down (the highest capacities, the widest slabs,
// first), block j = n_blocks - 1 - k holds the ranks whose first entry,
// in rank order, is one of entries [j * block, (j + 1) * block): an
// entity is never cut, ent_cum[r] counts the entries of the ranks
// before r, and the last block takes the empty entities after it.
inline void re_block_ranks(const int64_t* ent_cum, int64_t n_entities,
                           int64_t n_blocks, int64_t block, int64_t k,
                           int64_t* r0, int64_t* r1) {
  k = n_blocks - 1 - k;
  const int64_t* end = ent_cum + n_entities;
  *r0 = std::lower_bound(ent_cum, end, k * block) - ent_cum;
  *r1 = k + 1 == n_blocks
            ? n_entities
            : std::lower_bound(ent_cum, end, (k + 1) * block) - ent_cum;
}

inline int64_t re_n_blocks(const int64_t* ent_cum, int64_t n_entities,
                           int64_t block) {
  return std::max<int64_t>(1, (ent_cum[n_entities] + block - 1) / block);
}

// The distinct columns of examples ex_order[e0:e1], ascending.
inline void re_entity_columns(const int64_t* indptr, const int32_t* cols,
                              const int64_t* ex_order, int64_t e0,
                              int64_t e1, std::vector<int32_t>* out) {
  out->clear();
  for (int64_t j = e0; j < e1; ++j) {
    const int64_t i = ex_order[j];
    out->insert(out->end(), cols + indptr[i], cols + indptr[i + 1]);
  }
  std::sort(out->begin(), out->end());
  out->erase(std::unique(out->begin(), out->end()), out->end());
}

}  // namespace

extern "C" {

// First call.  indptr [n + 1] and cols [nnz]: the shard's CSR;
// ex_rank [n]: each example's entity.  Writes ex_start [n_entities + 1]
// and ex_order [n] (rank r's examples are ex_order[ex_start[r] :
// ex_start[r + 1]], in example order), ent_cum [n_entities + 1] and
// width [n_entities], the size of each entity's subspace.  Returns 0;
// -1: indptr is no CSR of nnz entries or a rank is out of range; -2:
// out of memory (the caller's numpy body decides).
int32_t pml_re_project_widths(const int64_t* indptr, const int32_t* cols,
                              int64_t n, int64_t nnz, const int64_t* ex_rank,
                              int64_t n_entities, int64_t* ex_start,
                              int64_t* ex_order, int64_t* ent_cum,
                              int32_t* width, int64_t block,
                              int32_t n_threads) {
  if (n < 0 || n_entities < 0 || block < 1 || indptr[0] < 0 ||
      indptr[n] > nnz)
    return -1;
  std::fill(ex_start, ex_start + n_entities + 1, 0);
  std::fill(ent_cum, ent_cum + n_entities + 1, 0);
  for (int64_t i = 0; i < n; ++i) {
    const int64_t r = ex_rank[i];
    if (r < 0 || r >= n_entities || indptr[i + 1] < indptr[i]) return -1;
    ++ex_start[r + 1];
    ent_cum[r + 1] += indptr[i + 1] - indptr[i];
  }
  for (int64_t r = 0; r < n_entities; ++r) {
    ex_start[r + 1] += ex_start[r];
    ent_cum[r + 1] += ent_cum[r];
  }
  // A counting sort by rank: ex_start[r] is the cursor of rank r while
  // the examples are dealt out, and is put back afterwards.
  for (int64_t i = 0; i < n; ++i) ex_order[ex_start[ex_rank[i]]++] = i;
  for (int64_t r = n_entities; r > 0; --r) ex_start[r] = ex_start[r - 1];
  ex_start[0] = 0;

  const int64_t n_blocks = re_n_blocks(ent_cum, n_entities, block);
  return for_each_block(n_blocks, n_threads, [&](int64_t k) {
    try {
      int64_t r0, r1;
      re_block_ranks(ent_cum, n_entities, n_blocks, block, k, &r0, &r1);
      std::vector<int32_t> seen;
      for (int64_t r = r0; r < r1; ++r) {
        re_entity_columns(indptr, cols, ex_order, ex_start[r],
                          ex_start[r + 1], &seen);
        width[r] = static_cast<int32_t>(seen.size());
      }
    } catch (const std::bad_alloc&) {
      return -2;
    }
    return 0;
  });
}

// Second call, with the first call's ex_start, ex_order and ent_cum.
// ex_pos [n]: an example's row in its entity's slab; bucket b holds
// the ranks [bucket_start[b], bucket_start[b + 1]) at capacity[b] rows
// and p[b] columns an entity; feature_ids[b]: int32 [entities, p[b]],
// all -1; x_blocks[b]: float32 [entities, capacity[b], p[b]], all 0.
// Returns 0; -1: a subspace wider than its bucket's p or a row outside
// its capacity (the outputs are then unspecified); -2: out of memory.
int32_t pml_re_project_fill(const int64_t* indptr, const int32_t* cols,
                            const float* vals, const int64_t* ex_pos,
                            int64_t n_entities, const int64_t* ex_start,
                            const int64_t* ex_order, const int64_t* ent_cum,
                            int64_t n_buckets, const int64_t* bucket_start,
                            const int64_t* capacity, const int64_t* p,
                            int32_t* const* feature_ids,
                            float* const* x_blocks, int64_t block,
                            int32_t n_threads) {
  if (n_entities < 0 || n_buckets < 0 || block < 1) return -1;
  const int64_t n_blocks = re_n_blocks(ent_cum, n_entities, block);
  return for_each_block(n_blocks, n_threads, [&](int64_t k) {
    try {
      int64_t r0, r1;
      re_block_ranks(ent_cum, n_entities, n_blocks, block, k, &r0, &r1);
      std::vector<int32_t> seen;
      int64_t b = std::upper_bound(bucket_start, bucket_start + n_buckets + 1,
                                   r0) - bucket_start - 1;
      for (int64_t r = r0; r < r1; ++r) {
        if (ent_cum[r + 1] == ent_cum[r]) continue;  // no entry: -1s, zeros
        while (b < n_buckets && r >= bucket_start[b + 1]) ++b;
        if (b < 0 || b >= n_buckets) return -1;
        re_entity_columns(indptr, cols, ex_order, ex_start[r],
                          ex_start[r + 1], &seen);
        const int64_t width = static_cast<int64_t>(seen.size());
        if (width > p[b]) return -1;
        const int64_t slot = r - bucket_start[b];
        std::memcpy(feature_ids[b] + slot * p[b], seen.data(),
                    seen.size() * sizeof(int32_t));
        float* slab = x_blocks[b] + slot * capacity[b] * p[b];
        for (int64_t j = ex_start[r]; j < ex_start[r + 1]; ++j) {
          const int64_t i = ex_order[j];
          if (ex_pos[i] < 0 || ex_pos[i] >= capacity[b]) return -1;
          float* row = slab + ex_pos[i] * p[b];
          // A canonical row's columns ascend, so each is found from
          // the one before; any other row searches from the start.
          auto from = seen.begin();
          int64_t before = -1;
          for (int64_t e = indptr[i]; e < indptr[i + 1]; ++e) {
            if (cols[e] <= before) from = seen.begin();
            from = std::lower_bound(from, seen.end(), cols[e]);
            row[from - seen.begin()] = vals[e];
            before = cols[e];
          }
        }
      }
    } catch (const std::bad_alloc&) {
      return -2;
    }
    return 0;
  });
}

}  // extern "C"
