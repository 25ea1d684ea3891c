/* Reference seeded watershed: a plain priority flood.
 *
 * Every voxel inside the mask takes the label of the seed reached by the
 * lexicographically smallest path key
 *
 *     (pass height = max h along the path, hop count, seed label)
 *
 * computed by Dijkstra's label-setting order over 4-connected neighbours of
 * one 2d slice (the per-slice watershed mode).  Voxels outside the mask
 * neither take a label nor conduct.  Built by harness/native.py with the
 * system C compiler; shares no code with the program under test.
 */
#include <stdint.h>
#include <stdlib.h>
#include <string.h>

typedef struct {
    float alt;
    int32_t hops;
    int32_t label;
    int64_t idx;
} entry;

static int less(const entry *a, const entry *b) {
    if (a->alt != b->alt) return a->alt < b->alt;
    if (a->hops != b->hops) return a->hops < b->hops;
    return a->label < b->label;
}

typedef struct {
    entry *e;
    int64_t n, cap;
} heap;

static int push(heap *h, entry v) {
    if (h->n == h->cap) {
        int64_t cap = h->cap ? 2 * h->cap : 1024;
        entry *e = realloc(h->e, (size_t)cap * sizeof(entry));
        if (!e) return -1;
        h->e = e;
        h->cap = cap;
    }
    int64_t i = h->n++;
    while (i > 0) {
        int64_t p = (i - 1) / 2;
        if (!less(&v, &h->e[p])) break;
        h->e[i] = h->e[p];
        i = p;
    }
    h->e[i] = v;
    return 0;
}

static entry pop(heap *h) {
    entry top = h->e[0];
    entry last = h->e[--h->n];
    int64_t i = 0;
    for (;;) {
        int64_t c = 2 * i + 1;
        if (c >= h->n) break;
        if (c + 1 < h->n && less(&h->e[c + 1], &h->e[c])) c++;
        if (!less(&h->e[c], &last)) break;
        h->e[i] = h->e[c];
        i = c;
    }
    if (h->n > 0) h->e[i] = last;
    return top;
}

/* One slice of ny*nx voxels.  Returns 0, or -1 when memory runs out. */
static int flood_slice(const float *hm, const int32_t *seeds,
                       const uint8_t *mask, int32_t *out, int64_t ny,
                       int64_t nx, float *alt, int32_t *hops, uint8_t *done,
                       heap *h) {
    int64_t n = ny * nx;
    h->n = 0;
    for (int64_t i = 0; i < n; i++) {
        out[i] = 0;
        done[i] = 0;
        alt[i] = 0.0f;
        hops[i] = INT32_MAX;
        if (mask[i] && seeds[i] > 0) {
            alt[i] = hm[i];
            hops[i] = 0;
            out[i] = seeds[i];
            entry v = {hm[i], 0, seeds[i], i};
            if (push(h, v)) return -1;
        }
    }
    while (h->n > 0) {
        entry p = pop(h);
        int64_t i = p.idx;
        if (done[i]) continue;
        if (p.alt != alt[i] || p.hops != hops[i] || p.label != out[i])
            continue; /* stale entry */
        done[i] = 1;
        int64_t y = i / nx, x = i % nx;
        int64_t nb[4];
        int k = 0;
        if (y > 0) nb[k++] = i - nx;
        if (y + 1 < ny) nb[k++] = i + nx;
        if (x > 0) nb[k++] = i - 1;
        if (x + 1 < nx) nb[k++] = i + 1;
        for (int j = 0; j < k; j++) {
            int64_t q = nb[j];
            if (!mask[q] || done[q]) continue;
            entry c = {p.alt > hm[q] ? p.alt : hm[q], p.hops + 1, p.label, q};
            entry cur = {alt[q], hops[q], out[q], q};
            if (out[q] == 0 || less(&c, &cur)) {
                alt[q] = c.alt;
                hops[q] = c.hops;
                out[q] = c.label;
                if (push(h, c)) return -1;
            }
        }
    }
    return 0;
}

/* nz independent slices of ny*nx voxels, C order.  Returns 0 on success. */
int flood_slices(const float *hm, const int32_t *seeds, const uint8_t *mask,
                 int32_t *out, int64_t nz, int64_t ny, int64_t nx) {
    int64_t n = ny * nx;
    float *alt = malloc((size_t)n * sizeof(float));
    int32_t *hops = malloc((size_t)n * sizeof(int32_t));
    uint8_t *done = malloc((size_t)n);
    heap h = {0, 0, 0};
    int rc = (alt && hops && done) ? 0 : -1;
    for (int64_t z = 0; z < nz && rc == 0; z++)
        rc = flood_slice(hm + z * n, seeds + z * n, mask + z * n, out + z * n,
                         ny, nx, alt, hops, done, &h);
    free(alt);
    free(hops);
    free(done);
    free(h.e);
    return rc;
}
