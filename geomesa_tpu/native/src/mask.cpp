// Compaction of a byte mask into the sorted indices of its nonzero bytes
// (np.flatnonzero). numpy's nonzero branches on every byte, so a mask
// whose hits are scattered over the rows costs a mispredicted branch a
// hit; this loop stores every index and advances the cursor by the
// byte's truth, with no branch on the data.
//
// Exported (ctypes):
//   geomesa_nonzero_u8(mask u8[n], n, out i64[cap], cap) -> count, or
//     -1 when cap < count + 1 (each step stores one slot ahead of the
//     cursor, so the caller sizes out as count_nonzero(mask) + 1)

#include <cstdint>

extern "C" int64_t geomesa_nonzero_u8(const uint8_t* mask, int64_t n,
                                      int64_t* out, int64_t cap) {
    int64_t k = 0;
    for (int64_t i = 0; i < n; ++i) {
        if (k >= cap) return -1;
        out[k] = i;
        k += mask[i] != 0;
    }
    return k;
}
