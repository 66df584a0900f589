/* Stable LSD radix argsort for the merge plane's packed 64-bit keys.
 *
 * The host sort path (ops/merge.py _host_sorted_winners_fast) spends
 * most of its time in np.argsort's comparison sort; an LSD radix sort
 * is O(n * passes) with sequential memory traffic and no comparisons —
 * ~3-4x faster at compaction scale on one core.  The native runtime
 * counterpart of the reference's JVM sorters (paimon-core
 * sort/BinaryInMemorySortBuffer + Arrays.sort loops), built as a plain
 * C ABI shared object loaded via ctypes (no CPython API).
 *
 * Byte passes whose value is constant across all keys are skipped
 * (normalized keys share sign/prefix bytes), so 8-byte keys usually
 * take 3-5 scatter passes instead of 8.
 *
 * radix_argsort_u64(keys, n, perm):
 *   keys : uint64_t[n]  input, unmodified
 *   n    : rows
 *   perm : int32_t[n]   output: stable ascending argsort of keys
 * returns 0 on success, -1 on allocation failure (caller falls back).
 */

#include <stdint.h>
#include <stdlib.h>
#include <string.h>

int radix_argsort_u64(const uint64_t *keys, int64_t n, int32_t *perm) {
    if (n <= 0) return 0;

    /* one histogram pass for all 8 byte positions */
    static const int P = 8;
    int64_t (*hist)[256] = calloc(P, sizeof(*hist));
    if (!hist) return -1;
    for (int64_t i = 0; i < n; i++) {
        uint64_t k = keys[i];
        for (int p = 0; p < P; p++)
            hist[p][(k >> (p * 8)) & 0xFF]++;
    }

    int active[8], n_active = 0;
    for (int p = 0; p < P; p++) {
        int constant = 0;
        for (int b = 0; b < 256; b++)
            if (hist[p][b] == n) { constant = 1; break; }
        if (!constant) active[n_active++] = p;
    }
    if (n_active == 0) {                    /* all keys identical */
        for (int64_t i = 0; i < n; i++) perm[i] = (int32_t)i;
        free(hist);
        return 0;
    }

    uint64_t *ka = malloc((size_t)n * sizeof(uint64_t));
    uint64_t *kb = malloc((size_t)n * sizeof(uint64_t));
    int32_t *pa = malloc((size_t)n * sizeof(int32_t));
    int32_t *pb = malloc((size_t)n * sizeof(int32_t));
    if (!ka || !kb || !pa || !pb) {
        free(ka); free(kb); free(pa); free(pb); free(hist);
        return -1;
    }

    const uint64_t *src_k = keys;           /* pass 1 reads the input */
    const int32_t *src_p = NULL;            /* implicit iota */
    uint64_t *dst_k = ka;
    int32_t *dst_p = pa;

    for (int a = 0; a < n_active; a++) {
        int p = active[a];
        int shift = p * 8;
        int64_t offs[256], acc = 0;
        for (int b = 0; b < 256; b++) { offs[b] = acc; acc += hist[p][b]; }

        if (src_p == NULL) {
            for (int64_t i = 0; i < n; i++) {
                uint64_t k = src_k[i];
                int64_t o = offs[(k >> shift) & 0xFF]++;
                dst_k[o] = k;
                dst_p[o] = (int32_t)i;
            }
        } else {
            for (int64_t i = 0; i < n; i++) {
                uint64_t k = src_k[i];
                int64_t o = offs[(k >> shift) & 0xFF]++;
                dst_k[o] = k;
                dst_p[o] = src_p[i];
            }
        }
        src_k = dst_k;
        src_p = dst_p;
        dst_k = (dst_k == ka) ? kb : ka;
        dst_p = (dst_p == pa) ? pb : pa;
    }

    memcpy(perm, src_p, (size_t)n * sizeof(int32_t));
    free(ka); free(kb); free(pa); free(pb); free(hist);
    return 0;
}

/* Fused entry: radix argsort + segmented winner selection without the
 * intermediate keys[perm] gather bouncing through Python.
 *   keys/seq : uint64_t[n] / int64_t[n] input
 *   perm     : int32_t[n] out — stable ascending key order
 *   winner   : uint8_t[n] out — winner[i]=1 iff sorted position i wins
 * returns 0, or -1 on allocation failure. */
int merge_winners_u64(const uint64_t *keys, const int64_t *seq,
                      int64_t n, int keep_last,
                      int32_t *perm, uint8_t *winner);

/* Segmented winners in one pass over radix-sorted keys: for each run of
 * equal keys pick the entry with max (seq, perm) [keep_last=1] or min
 * (seq, perm) [keep_last=0], writing a winner mask.  Fuses what the
 * Python path does with reduceat + three temporaries.
 *
 * sorted_keys/sorted_perm: the radix output order; seq indexed by perm.
 * winner: uint8_t[n] out (1 = winner of its segment, in sorted order).
 */
void segment_winners_i64(const uint64_t *sorted_keys,
                         const int32_t *sorted_perm,
                         const int64_t *seq, int64_t n, int keep_last,
                         uint8_t *winner) {
    if (n <= 0) return;
    memset(winner, 0, (size_t)n);
    int64_t best_i = 0;
    int64_t best_seq = seq[sorted_perm[0]];
    int32_t best_arr = sorted_perm[0];
    for (int64_t i = 1; i <= n; i++) {
        if (i == n || sorted_keys[i] != sorted_keys[i - 1]) {
            winner[best_i] = 1;
            if (i < n) {
                best_i = i;
                best_seq = seq[sorted_perm[i]];
                best_arr = sorted_perm[i];
            }
            continue;
        }
        int64_t s = seq[sorted_perm[i]];
        int32_t arr = sorted_perm[i];
        int better;
        if (keep_last)
            better = (s > best_seq) || (s == best_seq && arr > best_arr);
        else
            better = (s < best_seq) || (s == best_seq && arr < best_arr);
        if (better) { best_i = i; best_seq = s; best_arr = arr; }
    }
}

int merge_winners_u64(const uint64_t *keys, const int64_t *seq,
                      int64_t n, int keep_last,
                      int32_t *perm, uint8_t *winner) {
    if (n <= 0) return 0;
    int rc = radix_argsort_u64(keys, n, perm);
    if (rc != 0) return rc;
    uint64_t *sorted_keys = malloc((size_t)n * sizeof(uint64_t));
    if (!sorted_keys) return -1;
    for (int64_t i = 0; i < n; i++) sorted_keys[i] = keys[perm[i]];
    segment_winners_i64(sorted_keys, perm, seq, n, keep_last, winner);
    free(sorted_keys);
    return 0;
}

/* ------------------------------------------------------------------------
 * Offset-value coded k-way merge of sorted runs (Graefe et al., "Robust
 * and Efficient Sorting with Offset-Value Coding", arXiv 2209.08420).
 *
 * Replaces the O(n log n) sort of a merge window with an O(n log k)
 * tree-of-losers merge whose comparisons are SINGLE u64 integer
 * compares on the offset-value codes; only code ties fall through to
 * comparing the normalized-key lanes from the tied offset on.  Each
 * output row's final code is relative to the PREVIOUS output row, so
 * key-equality (segment boundaries for dedup/agg) falls out of the
 * merge for free — no neighbor-compare pass afterwards.
 *
 * Code layout for an L-lane u32 key row r relative to base row z:
 *   offset  = first lane where r differs from z (L = all lanes equal)
 *   code    = ((uint64_t)(L - offset) << 32) | r[offset]   (0 if equal)
 * Larger code = larger row (both rows >= z).  Ties beyond the lanes
 * break by (seq ascending, run index ascending) — run order is arrival
 * order, so the merged order equals the stable sort of the
 * concatenated input by (lanes..., seq, arrival).
 *
 * Inputs are the CONCATENATED runs: run j covers [starts[j],
 * starts[j+1]) and must be sorted by (lanes..., seq).  The initial
 * per-row codes (relative to the run predecessor; first row of a run
 * relative to an imaginary -infinity row at offset 0) come from
 * ovc_codes_u64 / ovc_codes_lanes below — one sequential pass that
 * also verifies the sort contract — and are passed in as ovc0.
 *
 * Outputs: perm[n] = original row indices in merged order;
 * code_out[n] = each output row's code relative to the previous
 * output (code_out[0] is relative to -infinity, never "equal").
 * Returns 0, or -1 on allocation failure (caller falls back).
 * --------------------------------------------------------------------- */

typedef struct {
    const uint32_t *lanes;   /* [n*L] row-major; NULL for the u64 path */
    const uint64_t *keys;    /* [n] packed keys; NULL for the lane path */
    const int64_t *seq;
    int64_t L;               /* logical lane count (2 for the u64 path) */
    int64_t *pos;            /* per-run cursor (absolute row index) */
    const int64_t *end;      /* per-run end (absolute) */
    uint64_t *code;          /* per-run current candidate code */
} ovc_ctx;

/* lane l of row i (the u64 path views the key as two big-endian u32
 * lanes so one code layout serves both entries) */
static inline uint32_t ovc_lane(const ovc_ctx *c, int64_t i, int64_t l) {
    if (c->keys)
        return (uint32_t)(l == 0 ? (c->keys[i] >> 32)
                                 : (c->keys[i] & 0xFFFFFFFFu));
    return c->lanes[i * c->L + l];
}

/* 1 iff run a's candidate precedes run b's.  Codes of both candidates
 * are relative to the same base (the last row that won at the tree
 * node where they meet — the tree-of-losers invariant); on unequal
 * codes the loser's code is already valid relative to the winner, on
 * equal codes the lanes are compared from the tied offset on and the
 * loser's code is recomputed relative to the winner. */
static inline int ovc_wins(ovc_ctx *c, int64_t a, int64_t b) {
    if (c->pos[a] >= c->end[a]) return 0;
    if (c->pos[b] >= c->end[b]) return 1;
    uint64_t ca = c->code[a], cb = c->code[b];
    if (ca != cb) return ca < cb;
    int64_t ia = c->pos[a], ib = c->pos[b];
    int64_t L = c->L;
    /* equal codes: rows agree with each other up to AND including the
     * code's offset; compare the remaining lanes */
    int64_t off = L - (int64_t)(ca >> 32);     /* code 0 -> off == L */
    for (int64_t l = off + 1; l < L; l++) {
        uint32_t va = ovc_lane(c, ia, l), vb = ovc_lane(c, ib, l);
        if (va != vb) {
            int a_wins = va < vb;
            int64_t lose_i = a_wins ? ib : ia;
            c->code[a_wins ? b : a] =
                ((uint64_t)(L - l) << 32) | ovc_lane(c, lose_i, l);
            return a_wins;
        }
    }
    /* keys fully equal: loser is equal to the winner (code 0); order
     * by (seq, run index) — run order is arrival order */
    int a_wins;
    if (c->seq[ia] != c->seq[ib]) a_wins = c->seq[ia] < c->seq[ib];
    else a_wins = a < b;
    c->code[a_wins ? b : a] = 0;
    return a_wins;
}

/* Initial per-run codes + sort-contract verification in ONE sequential
 * pass (the vectorized numpy equivalent costs more than the merge
 * itself at window scale).  Returns 0, or -1 when a run is not
 * actually (key, seq)-ascending — the caller falls back to the sort
 * paths instead of producing a wrong merge. */
int ovc_codes_u64(const uint64_t *keys, const int64_t *seq,
                  const int64_t *starts, int64_t k, uint64_t *codes) {
    for (int64_t j = 0; j < k; j++) {
        int64_t s = starts[j], e = starts[j + 1];
        if (e <= s) continue;
        codes[s] = (2ull << 32) | (keys[s] >> 32);
        for (int64_t i = s + 1; i < e; i++) {
            uint64_t a = keys[i - 1], b = keys[i];
            if (b < a) return -1;
            if (a == b) {
                if (seq[i] < seq[i - 1]) return -1;
                codes[i] = 0;
            } else if ((b >> 32) != (a >> 32)) {
                codes[i] = (2ull << 32) | (b >> 32);
            } else {
                codes[i] = (1ull << 32) | (uint32_t)b;
            }
        }
    }
    return 0;
}

int ovc_codes_lanes(const uint32_t *lanes, const int64_t *seq,
                    const int64_t *starts, int64_t k, int64_t L,
                    uint64_t *codes) {
    for (int64_t j = 0; j < k; j++) {
        int64_t s = starts[j], e = starts[j + 1];
        if (e <= s) continue;
        codes[s] = ((uint64_t)L << 32) | lanes[s * L];
        for (int64_t i = s + 1; i < e; i++) {
            const uint32_t *a = lanes + (i - 1) * L;
            const uint32_t *b = lanes + i * L;
            int64_t l = 0;
            while (l < L && a[l] == b[l]) l++;
            if (l == L) {
                if (seq[i] < seq[i - 1]) return -1;
                codes[i] = 0;
            } else {
                if (b[l] < a[l]) return -1;
                codes[i] = ((uint64_t)(L - l) << 32) | b[l];
            }
        }
    }
    return 0;
}

/* Small-k variant: a linear min-scan over the k candidate codes beats
 * the tree's branch-misprediction-heavy replay for the run counts
 * compaction actually sees (k <= ~16).  All candidate codes are kept
 * relative to the LAST OUTPUT row: the minimum wins; candidates tied
 * on the winning code are resolved by lane/seq compares and then
 * re-coded relative to the final winner (codes strictly above the
 * minimum stay valid unchanged — the loser-update rule). */
static int ovc_merge_scan(ovc_ctx *c, int64_t k, int64_t n,
                          const uint64_t *ovc0,
                          int32_t *perm, uint64_t *code_out) {
    int64_t tied[64];
    for (int64_t out = 0; out < n; out++) {
        uint64_t best = c->code[0];
        int64_t w = 0;
        for (int64_t j = 1; j < k; j++) {     /* branchless min scan */
            uint64_t cj = c->code[j];
            int lt = cj < best;
            best = lt ? cj : best;
            w = lt ? j : w;
        }
        int64_t n_tied = 0;
        for (int64_t j = w + 1; j < k; j++)
            if (c->code[j] == best) tied[n_tied++] = j;
        if (n_tied && best != UINT64_MAX) {
            tied[n_tied++] = w;            /* full tie set, w included */
            for (int64_t t = 0; t < n_tied - 1; t++)
                if (!ovc_wins(c, w, tied[t])) w = tied[t];
            /* re-code every tied loser relative to the FINAL winner
             * (an intermediate comparison may have coded it against a
             * candidate that then lost) */
            for (int64_t t = 0; t < n_tied; t++)
                if (tied[t] != w) {
                    c->code[tied[t]] = best;   /* restore the tie... */
                    ovc_wins(c, w, tied[t]);   /* ...and code vs w */
                }
        }
        perm[out] = (int32_t)c->pos[w];
        code_out[out] = c->code[w];
        c->pos[w]++;
        c->code[w] = c->pos[w] < c->end[w] ? ovc0[c->pos[w]]
                                           : UINT64_MAX;
    }
    return 0;
}

static int ovc_merge_run(const uint32_t *lanes, const uint64_t *keys,
                         const int64_t *seq, const uint64_t *ovc0,
                         const int64_t *starts, int64_t k, int64_t n,
                         int64_t L, int32_t *perm, uint64_t *code_out) {
    if (n <= 0) return 0;
    if (k <= 64) {
        int64_t pos_s[64], end_s[64];
        uint64_t code_s[64];
        for (int64_t j = 0; j < k; j++) {
            pos_s[j] = starts[j];
            end_s[j] = starts[j + 1];
            code_s[j] = pos_s[j] < end_s[j] ? ovc0[pos_s[j]]
                                            : UINT64_MAX;
        }
        ovc_ctx c = { lanes, keys, seq, L, pos_s, end_s, code_s };
        return ovc_merge_scan(&c, k, n, ovc0, perm, code_out);
    }
    int64_t m = 1;
    while (m < k) m <<= 1;
    int64_t *pos = malloc((size_t)m * sizeof(int64_t));
    int64_t *end = malloc((size_t)m * sizeof(int64_t));
    uint64_t *code = malloc((size_t)m * sizeof(uint64_t));
    int64_t *win = malloc((size_t)(2 * m) * sizeof(int64_t));
    int64_t *lose = malloc((size_t)m * sizeof(int64_t));
    if (!pos || !end || !code || !win || !lose) {
        free(pos); free(end); free(code); free(win); free(lose);
        return -1;
    }
    ovc_ctx c = { lanes, keys, seq, L, pos, end, code };
    for (int64_t j = 0; j < m; j++) {
        pos[j] = j < k ? starts[j] : n;
        end[j] = j < k ? starts[j + 1] : n;
        code[j] = pos[j] < end[j] ? ovc0[pos[j]] : UINT64_MAX;
    }
    /* build: winner tree bottom-up, keeping each node's loser */
    for (int64_t j = 0; j < m; j++) win[m + j] = j;
    for (int64_t v = m - 1; v >= 1; v--) {
        int64_t a = win[2 * v], b = win[2 * v + 1];
        int aw = ovc_wins(&c, a, b);
        win[v] = aw ? a : b;
        lose[v] = aw ? b : a;
    }
    int64_t w = win[1];
    for (int64_t out = 0; out < n; out++) {
        perm[out] = (int32_t)pos[w];
        code_out[out] = code[w];
        pos[w]++;
        code[w] = pos[w] < end[w] ? ovc0[pos[w]] : UINT64_MAX;
        for (int64_t v = (m + w) >> 1; v >= 1; v >>= 1) {
            if (!ovc_wins(&c, w, lose[v])) {
                int64_t t = lose[v];
                lose[v] = w;
                w = t;
            }
        }
    }
    free(pos); free(end); free(code); free(win); free(lose);
    return 0;
}

int ovc_merge_u64(const uint64_t *keys, const int64_t *seq,
                  const uint64_t *ovc0, const int64_t *starts,
                  int64_t k, int64_t n,
                  int32_t *perm, uint64_t *code_out) {
    return ovc_merge_run(NULL, keys, seq, ovc0, starts, k, n, 2,
                         perm, code_out);
}

int ovc_merge_lanes(const uint32_t *lanes, const int64_t *seq,
                    const uint64_t *ovc0, const int64_t *starts,
                    int64_t k, int64_t n, int64_t L,
                    int32_t *perm, uint64_t *code_out) {
    return ovc_merge_run(lanes, NULL, seq, ovc0, starts, k, n, L,
                         perm, code_out);
}
